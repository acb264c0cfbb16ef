"""Jit'd public wrappers around the Pallas kernels.

Handles the unglamorous production parts: padding to tile multiples,
complex GEMMs for the quantum executor (3-real-GEMM Karatsuba — a
beyond-paper trick: 25% fewer MXU FLOPs than the naive 4-GEMM form), GQA
head broadcast for flash attention, and the SSD inter-chunk combine.

``interpret`` defaults to True off-TPU so the same call sites run the
kernel bodies on CPU (correctness) and the compiled kernels on TPU
(performance).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .contract_gemm import tiled_matmul
from .flash_attention import flash_attention
from .mamba2_ssd import ssd_intra_chunk


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
    min_kernel_dim: int = 128,
    precision: str = "fp32",
) -> jax.Array:
    """GEMM via the Pallas kernel, with padding and complex support.

    Falls back to jnp.dot for tiny shapes where tile padding would dominate
    (the paper's Sec. V-A pathology — better to merge branches than to run
    a 128×4 GEMM on the MXU).

    ``precision="bf16"`` rounds the (real-component) operands to bf16
    before the kernel; the MXU accumulates in fp32 and the output stays
    fp32.  Complex Karatsuba sums its component pairs in fp32 *before*
    the rounding, so the fused/chained paths can match bitwise.
    """
    if interpret is None:
        interpret = default_interpret()
    if jnp.iscomplexobj(a) or jnp.iscomplexobj(b):
        return _complex_matmul(
            a, b, bm=bm, bn=bn, bk=bk, interpret=interpret,
            min_kernel_dim=min_kernel_dim, precision=precision,
        )
    m, k = a.shape
    _, n = b.shape
    if min(m, n, k) < min_kernel_dim:
        return ref.matmul_ref(a, b)
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    if precision == "bf16":
        ap = ap.astype(jnp.bfloat16)
        bp = bp.astype(jnp.bfloat16)
    out = tiled_matmul(ap, bp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


def _complex_matmul(
    a: jax.Array, b: jax.Array, **kw
) -> jax.Array:
    """Karatsuba: 3 real GEMMs instead of 4.

    P1 = Ar·Br, P2 = Ai·Bi, P3 = (Ar+Ai)·(Br+Bi)
    C  = (P1 − P2) + i·(P3 − P1 − P2)
    """
    ar, ai = jnp.real(a).astype(jnp.float32), jnp.imag(a).astype(jnp.float32)
    br, bi = jnp.real(b).astype(jnp.float32), jnp.imag(b).astype(jnp.float32)
    p1 = matmul(ar, br, **kw)
    p2 = matmul(ai, bi, **kw)
    p3 = matmul(ar + ai, br + bi, **kw)
    return (p1 - p2) + 1j * (p3 - p1 - p2)


def attention(
    q: jax.Array,  # (batch, seq_q, n_heads, d)
    k: jax.Array,  # (batch, seq_k, n_kv, d)
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Multi-head attention with GQA, (b, s, h, d) layout.

    The kernel path broadcasts KV heads to Q heads and flattens (b, h);
    decode paths (seq_q below tile size) use the reference (they are
    bandwidth-, not compute-bound)."""
    if interpret is None:
        interpret = default_interpret()
    batch, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    if (
        not use_kernel
        or sq % bq
        or sk % bk
        or q_offset % bq
        or d % 8
    ):
        # reference path (decode steps, ragged shapes)
        qf = q.transpose(0, 2, 1, 3).reshape(batch * hq, sq, d)
        kf = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1).reshape(
            batch * hq, sk, d
        )
        vf = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1).reshape(
            batch * hq, sk, d
        )
        o = ref.attention_ref(qf, kf, vf, causal=causal, q_offset=q_offset)
        return o.reshape(batch, hq, sq, d).transpose(0, 2, 1, 3)
    qf = q.transpose(0, 2, 1, 3).reshape(batch * hq, sq, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1).reshape(
        batch * hq, sk, d
    )
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1).reshape(
        batch * hq, sk, d
    )
    o = flash_attention(
        qf, kf, vf, bq=bq, bk=bk, causal=causal, q_offset=q_offset,
        interpret=interpret,
    )
    return o.reshape(batch, hq, sq, d).transpose(0, 2, 1, 3)


def ssd_scan(
    x: jax.Array,  # (BH, T, D)
    dt: jax.Array,  # (BH, T)
    a: jax.Array,  # (BH, T) per-step log decay
    b: jax.Array,  # (BH, T, S)
    c: jax.Array,  # (BH, T, S)
    *,
    chunk: int = 64,
    state0: jax.Array | None = None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD: Pallas intra-chunk + lax.scan inter-chunk combine.

    Returns (y (BH,T,D) fp32, final_state (BH,S,D) fp32).
    """
    if interpret is None:
        interpret = default_interpret()
    BH, T, D = x.shape
    S = b.shape[-1]
    if not use_kernel or T % chunk:
        return ref.ssd_scan_ref(x, dt, a, b, c, state0)
    C = T // chunk
    xr = x.reshape(BH, C, chunk, D)
    dtr = dt.reshape(BH, C, chunk)
    ar = a.reshape(BH, C, chunk).astype(jnp.float32)
    br = b.reshape(BH, C, chunk, S)
    cr = c.reshape(BH, C, chunk, S)
    y_intra, chunk_states = ssd_intra_chunk(
        xr, dtr, ar, br, cr, interpret=interpret
    )
    # inter-chunk recurrence over C steps
    cum_a = jnp.cumsum(ar, axis=2)  # (BH, C, L)
    chunk_decay = jnp.exp(cum_a[:, :, -1])  # (BH, C) total decay of chunk
    h0 = (
        jnp.zeros((BH, S, D), jnp.float32)
        if state0 is None
        else state0.astype(jnp.float32)
    )

    def step(h, inp):
        st_c, decay_c = inp  # (BH,S,D), (BH,)
        h_in = h  # state entering this chunk
        h_out = decay_c[:, None, None] * h + st_c
        return h_out, h_in

    states_seq = (
        jnp.moveaxis(chunk_states, 1, 0),
        jnp.moveaxis(chunk_decay, 1, 0),
    )
    h_final, h_ins = jax.lax.scan(step, h0, states_seq)
    h_ins = jnp.moveaxis(h_ins, 0, 1)  # (BH, C, S, D) state entering chunk
    # cross-chunk contribution: y_t += c_t · (decay_to_t · h_in)
    decay_to_t = jnp.exp(cum_a)  # (BH, C, L) decay from chunk start to t
    y_cross = jnp.einsum(
        "bcls,bcsd,bcl->bcld", cr.astype(jnp.float32), h_ins, decay_to_t
    )
    y = (y_intra + y_cross).reshape(BH, T, D)
    return y, h_final
