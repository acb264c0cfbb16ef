"""Tiled stem-contraction GEMM — the paper's compute hot-spot, TPU-native.

The contraction of two stem tensors is a (2^m × 2^k) @ (2^k × 2^n) GEMM
(Sec. V-A).  On Sunway the paper fights SWTT's 8×8 kernel quantization and
DMA bandwidth; the TPU analogue is MXU 128×128 tile quantization and
HBM→VMEM bandwidth.  This kernel:

  * tiles (bm × bk) @ (bk × bn) blocks into VMEM via BlockSpec — block
    shapes are chosen 128-aligned so the MXU sees full tiles,
  * walks K as the innermost (sequential) grid axis, accumulating into the
    revisited output block in fp32 (``preferred_element_type``) — the
    bf16-compute/fp32-accumulate mixed precision the paper uses on Sunway
    (fp16/fp32) mapped to the TPU-native pair,
  * leaves M as the outermost axis so slice-batched stems (executor vmap)
    stream through without re-fetching B.

Validated against ref.matmul_ref in interpret mode on the CPU, and
compiled for a described v5e in ``tests/test_chip_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _matmul_kernel(a_ref, b_ref, o_ref, *, k_tiles: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # f32 operands ask for the multi-pass fp32 MXU product: the default
    # would round them to bf16; bf16 operands take the single pass
    precision = (
        jax.lax.Precision.HIGHEST if a_ref.dtype == jnp.float32 else None
    )
    o_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=precision,
    )


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def tiled_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with fp32 accumulation.  Dims must divide the block shape
    (ops.matmul pads); returns fp32."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, k, n),
        (bm, bk, bn),
    )
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_tiles=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="tiled_matmul",  # a stable kernel name for profiles
    )(a, b)
