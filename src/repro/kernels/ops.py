"""jit'd public wrappers for the Pallas kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.linrec_mm import linrec_blocked_scan, linrec_scan_tiles
from repro.kernels.scan_mm import scan_tiles
from repro.kernels.scan_pipeline import blocked_scan
from repro.kernels.segscan_mm import seg_blocked_scan, seg_scan_tiles
from repro.kernels.split_mm import (
    multi_split_tiles,
    radix_pass_multibit,
    split_tiles,
    topp_mask_sample_tiles,
)
from repro.kernels.ssd_chunk import ssd_chunk_scan

__all__ = ["scan_kernel", "blocked_scan_kernel", "ssd_kernel", "split_kernel",
           "multi_split_kernel", "radix_pass_kernel", "radix_sort_enc_kernel",
           "topp_mask_sample_kernel", "seg_scan_kernel",
           "seg_blocked_scan_kernel", "linrec_kernel",
           "linrec_blocked_kernel"]


@functools.partial(jax.jit, static_argnames=("s", "variant", "accum_dtype",
                                             "interpret", "precision"))
def scan_kernel(x: jax.Array, *, s: int = 128, variant: str = "scanul1",
                accum_dtype=None, interpret: bool | None = None,
                precision: str = "highest") -> jax.Array:
    """Fused matmul-scan over the last axis (ScanU/ScanUL1, paper Alg. 1/2)."""
    return scan_tiles(x, s=s, variant=variant, accum_dtype=accum_dtype,
                      interpret=interpret, precision=precision)


@functools.partial(jax.jit, static_argnames=("s", "block_tiles", "variant",
                                             "accum_dtype", "interpret",
                                             "precision"))
def blocked_scan_kernel(x: jax.Array, *, s: int = 128, block_tiles: int = 8,
                        variant: str = "scanul1", accum_dtype=None,
                        interpret: bool | None = None,
                        precision: str = "highest") -> jax.Array:
    """Three-phase blocked scan pipeline (paper §4 MCScan, one device)."""
    return blocked_scan(x, s=s, block_tiles=block_tiles, variant=variant,
                        accum_dtype=accum_dtype, interpret=interpret,
                        precision=precision)


@functools.partial(jax.jit, static_argnames=("s", "accum_dtype", "interpret",
                                             "precision"))
def seg_scan_kernel(x: jax.Array, flags: jax.Array, *, s: int = 128,
                    accum_dtype=None, interpret: bool | None = None,
                    precision: str = "highest") -> jax.Array:
    """Fused segmented matmul scan: carry resets at flagged boundaries."""
    return seg_scan_tiles(x, flags, s=s, accum_dtype=accum_dtype,
                          interpret=interpret, precision=precision)


@functools.partial(jax.jit, static_argnames=("s", "block_tiles",
                                             "accum_dtype", "interpret",
                                             "precision"))
def seg_blocked_scan_kernel(x: jax.Array, flags: jax.Array, *, s: int = 128,
                            block_tiles: int = 8, accum_dtype=None,
                            interpret: bool | None = None,
                            precision: str = "highest") -> jax.Array:
    """§4 blocked pipeline with a segmented phase-2 carry scan."""
    return seg_blocked_scan(x, flags, s=s, block_tiles=block_tiles,
                            accum_dtype=accum_dtype, interpret=interpret,
                            precision=precision)


@functools.partial(jax.jit, static_argnames=("s", "accum_dtype", "interpret",
                                             "precision"))
def linrec_kernel(a: jax.Array, b: jax.Array, *, s: int = 128,
                  accum_dtype=None, interpret: bool | None = None,
                  precision: str = "highest") -> jax.Array:
    """Fused linear-recurrence tile scan (running state carried in VMEM)."""
    return linrec_scan_tiles(a, b, s=s, accum_dtype=accum_dtype,
                             interpret=interpret, precision=precision)


@functools.partial(jax.jit, static_argnames=("s", "block_tiles",
                                             "accum_dtype", "interpret",
                                             "precision"))
def linrec_blocked_kernel(a: jax.Array, b: jax.Array, *, s: int = 128,
                          block_tiles: int = 8, accum_dtype=None,
                          interpret: bool | None = None,
                          precision: str = "highest") -> jax.Array:
    """§4 blocked pipeline with an affine phase-2 carry scan."""
    return linrec_blocked_scan(a, b, s=s, block_tiles=block_tiles,
                               accum_dtype=accum_dtype, interpret=interpret,
                               precision=precision)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_kernel(x, a_log, b_mat, c_mat, *, chunk: int = 128,
               interpret: bool | None = None):
    """Fused chunked SSD scan (gated linear recurrence on the MXU)."""
    return ssd_chunk_scan(x, a_log, b_mat, c_mat, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def split_kernel(x: jax.Array, flags: jax.Array, *, s: int = 128,
                 interpret: bool | None = None):
    """Fused SplitInd (paper §5): ``(z, indices, n_true)`` in one launch/row."""
    return split_tiles(x, flags, s=s, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("num_buckets", "s", "interpret"))
def multi_split_kernel(x: jax.Array, digits: jax.Array, *, num_buckets: int,
                       s: int = 128, interpret: bool | None = None):
    """Fused radix-2^k SplitInd: ``(z, indices, counts)`` in one launch/row."""
    return multi_split_tiles(x, digits, num_buckets=num_buckets, s=s,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("shift", "pass_bits", "s",
                                             "interpret", "with_counts"))
def radix_pass_kernel(work: jax.Array, perm: jax.Array, *, shift: int,
                      pass_bits: int, s: int = 128,
                      interpret: bool | None = None,
                      with_counts: bool = False):
    """One fused radix-2^k pass; ``with_counts`` exports the digit histogram.

    Thin jitted wrapper over :func:`repro.kernels.split_mm.radix_pass_multibit`
    — the per-shard pass of the distributed sort (``repro.core.dist_ops``)
    calls this with ``with_counts=True`` so the bucket-exchange bases come out
    of the same launch that groups the shard.
    """
    return radix_pass_multibit(work, perm, shift=shift, pass_bits=pass_bits,
                               s=s, interpret=interpret,
                               with_counts=with_counts)


@functools.partial(jax.jit, static_argnames=("bits", "bits_per_pass", "s",
                                             "interpret"))
def radix_sort_enc_kernel(enc: jax.Array, *, bits: int, bits_per_pass: int = 1,
                          s: int = 128, interpret: bool | None = None):
    """Stable LSB radix sort of an unsigned encoding via fused radix passes.

    ``enc``: (..., n) unsigned keys (see ``primitives._encode_for_sort``).
    Returns ``(sorted_enc, permutation)``.  One ``radix_pass_multibit`` pass
    per ``bits_per_pass``-bit digit — ``ceil(bits / bits_per_pass)`` passes
    total (a ragged final digit just uses the remaining bits).
    """
    *lead, n = enc.shape
    work = enc.reshape(-1, n)
    perm = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), work.shape)
    for shift in range(0, bits, bits_per_pass):
        k = min(bits_per_pass, bits - shift)
        work, perm = radix_pass_multibit(work, perm, shift=shift, pass_bits=k,
                                         s=s, interpret=interpret)
    return work.reshape(*lead, n), perm.reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("p", "s", "interpret"))
def topp_mask_sample_kernel(sorted_p: jax.Array, u: jax.Array, *, p: float,
                            s: int = 128,
                            interpret: bool | None = None) -> jax.Array:
    """Fused nucleus-sampling tail: index into the sorted order, one launch."""
    return topp_mask_sample_tiles(sorted_p, u, p=p, s=s, interpret=interpret)
