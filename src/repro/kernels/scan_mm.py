"""Fused Pallas TPU kernel for the paper's ScanU / ScanUL1 tile scans.

One kernel launch scans a whole (batch of) array(s): the grid is ``(batch, n_tiles)``
and TPU executes the tile dimension sequentially on a core, which gives us exactly the
paper's pipelined single-core loop (Alg. 1/2) — the MTE double-buffering of AscendC
queues is performed by the Pallas pipeline from ``BlockSpec``, and the running
``partial`` lives in a one-row VMEM scratch instead of a vector-core register.

Beyond-paper fusion: on Ascend the cube core writes the tile to GM and a *separate*
vector core re-reads it to add the carry (two extra GM trips).  On TPU the MXU and VPU
share VMEM, so the carry add is fused after the matmuls — the kernel moves 2N bytes
total, the theoretical minimum for scan.

dtypes follow the cube unit: fp32, bf16 (fp32 accumulate), int8 (int32 accumulate —
the paper's mask-scan specialization); int32 payloads run in interpret mode
only, since Mosaic has no int32 matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import guards
from repro.core.scan import _operand_dtype, accum_dtype_for
from repro.kernels.tile import default_interpret, last_as_row, tile_scan

__all__ = ["scan_tiles", "scan_mm_kernel", "VARIANTS"]

# The two tile-scan algorithms of the paper (Alg. 1 ScanU / Alg. 2 ScanUL1).
VARIANTS = ("scanul1", "scanu")


def _kernel(x_ref, o_ref, carry_ref, *, variant: str, acc, precision: str):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, acc)

    # U_s / L⁻_s are built in-register from iota comparisons instead of being
    # streamed from HBM, so the only HBM traffic left is the tile itself.
    out = tile_scan(x_ref[0, 0], variant=variant, acc=acc,
                    precision=precision) + carry_ref[...]
    carry_ref[...] = last_as_row(out)
    o_ref[0, 0] = out


def scan_mm_kernel(variant: str, acc, s: int, interpret: bool,
                   precision: str = "highest"):
    kern = functools.partial(_kernel, variant=variant, acc=acc,
                             precision=precision)

    def call(tiles: jax.Array) -> jax.Array:
        b, nt = tiles.shape[0], tiles.shape[1]
        return pl.pallas_call(
            kern,
            grid=(b, nt),
            in_specs=[
                pl.BlockSpec((1, 1, s, s), lambda i, j: (i, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, s, s), lambda i, j: (i, j, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((b, nt, s, s), acc),
            scratch_shapes=[pltpu.VMEM((1, s), acc)],
            interpret=interpret,
            name=f"scan_mm_{variant}_s{s}",
        )(tiles)

    return call


def scan_tiles(x: jax.Array, *, s: int = 128, variant: str = "scanul1",
               accum_dtype=None, interpret: bool | None = None,
               precision: str = "highest") -> jax.Array:
    """Scan the last axis of ``x`` (any leading batch dims) with the fused kernel."""
    variant = guards.validate_choice(variant, VARIANTS, name="variant",
                                     op="scan_tiles")
    s = guards.validate_positive(s, name="s", op="scan_tiles")
    interpret = default_interpret(interpret)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else accum_dtype_for(x.dtype)
    *lead, n = x.shape
    ell = s * s
    xb = x.reshape(-1, n) if lead else x[None]
    if xb.dtype == jnp.bool_:
        xb = xb.astype(_operand_dtype(xb.dtype))
    b = xb.shape[0]
    pad = (-n) % ell
    if pad:
        xb = jnp.pad(xb, ((0, 0), (0, pad)))
    nt = xb.shape[-1] // ell
    tiles = xb.reshape(b, nt, s, s)
    out = scan_mm_kernel(variant, acc, s, interpret, precision)(tiles)
    out = out.reshape(b, nt * ell)[:, :n]
    return out.reshape(*lead, n) if lead else out[0]
