"""Pallas TPU kernels for the paper's compute hot-spots (Mosaic on a TPU, interpret mode elsewhere)."""
from repro.kernels.ops import (
    scan_kernel, blocked_scan_kernel, ssd_kernel, split_kernel,
    multi_split_kernel, radix_sort_enc_kernel, topp_mask_sample_kernel,
    seg_scan_kernel, seg_blocked_scan_kernel, linrec_kernel,
    linrec_blocked_kernel,
)
