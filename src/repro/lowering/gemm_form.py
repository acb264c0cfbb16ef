"""GEMM normalization: pairwise contraction → transpose/reshape/GEMM form.

The paper's Sec. V-A observation is that every stem contraction *is* a
GEMM once its indices are classified; the Sunway runtime rewrites each
pairwise contraction into a fused transpose→GEMM so the hot loop never
executes a generic einsum.  This module is the TPU analogue of that
rewrite: given the (ordered) index tuples of one contraction step it
classifies every index into one of four GEMM roles,

  batch  — shared by both operands AND kept in the output (open sampling
           indices that ride through both children; lowered as the
           leading batch axis of a batched GEMM),
  M      — kept indices exclusive to the left operand,
  N      — kept indices exclusive to the right operand,
  K      — contracted indices (shared, absent from the output),

and emits a static :class:`GemmForm`: two input permutations, the
(B, M, K) / (B, K, N) collapse shapes, and the output permutation that
restores the executor's index-order convention.  Sliced indices never
reach this layer — the executor fixes them on the leaf arrays before any
step runs — so a slicing mask ``S`` only shrinks the shapes seen here
(the "sliced indices as fixed axes" half of the paper's rewrite).

:func:`apply` executes a refined step (:class:`~repro.lowering.refiner.
GemmSpec`) inside the jitted slice program.  Complex operands on the
Pallas backend route through the 3-real-GEMM Karatsuba in
:mod:`repro.kernels.ops`; tiny/degenerate nodes keep the original einsum
string so lowering is total over arbitrary trees.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Hashable, Sequence

import jax
import jax.numpy as jnp


def real_component_bytes(dtype) -> int:
    """Byte width of one real component (complex64 → 4, complex128 → 8).

    The single source of the Pallas-safety policy: components wider than
    4 bytes must not run through the fp32-accumulating kernel — the
    refiner routes them off Pallas at plan time and :func:`apply`
    re-checks the concrete arrays at trace time.
    """
    dt = jnp.dtype(dtype)
    return (
        dt.itemsize // 2 if jnp.issubdtype(dt, jnp.complexfloating)
        else dt.itemsize
    )


@dataclasses.dataclass(frozen=True)
class GemmForm:
    """Static lowering of one pairwise contraction to batched-GEMM form."""

    inds_a: tuple
    inds_b: tuple
    inds_out: tuple
    batch_inds: tuple
    m_inds: tuple
    n_inds: tuple
    k_inds: tuple
    perm_a: tuple[int, ...]  # a axes → (batch..., m..., k...)
    perm_b: tuple[int, ...]  # b axes → (batch..., k..., n...)
    out_perm: tuple[int, ...]  # (batch..., m..., n...) → inds_out order
    batch_shape: tuple[int, ...]
    m_shape: tuple[int, ...]
    n_shape: tuple[int, ...]
    k_shape: tuple[int, ...]
    expr: str  # einsum fallback for the same step

    @property
    def B(self) -> int:
        return math.prod(self.batch_shape)

    @property
    def M(self) -> int:
        return math.prod(self.m_shape)

    @property
    def N(self) -> int:
        return math.prod(self.n_shape)

    @property
    def K(self) -> int:
        return math.prod(self.k_shape)

    @property
    def flops(self) -> float:
        """Real-valued multiply-add count of the un-padded GEMM."""
        return 2.0 * self.B * self.M * self.N * self.K


def lower_step(
    inds_a: Sequence[Hashable],
    inds_b: Sequence[Hashable],
    inds_out: Sequence[Hashable],
    size_of: Callable[[Hashable], int],
) -> GemmForm:
    """Classify one pairwise contraction into GEMM roles.

    ``inds_out`` must follow the executor's convention (kept indices of
    ``a`` in order, then kept indices of ``b`` not already present), i.e.
    the output of :func:`repro.core.executor.pair_contract_inds`.
    """
    set_a, set_b = set(inds_a), set(inds_b)
    out_set = set(inds_out)
    batch = tuple(ix for ix in inds_a if ix in set_b and ix in out_set)
    k_inds = tuple(ix for ix in inds_a if ix in set_b and ix not in out_set)
    m_inds = tuple(ix for ix in inds_a if ix not in set_b)
    n_inds = tuple(ix for ix in inds_b if ix not in set_a)

    pos_a = {ix: i for i, ix in enumerate(inds_a)}
    pos_b = {ix: i for i, ix in enumerate(inds_b)}
    perm_a = tuple(pos_a[ix] for ix in batch + m_inds + k_inds)
    perm_b = tuple(pos_b[ix] for ix in batch + k_inds + n_inds)

    natural = batch + m_inds + n_inds
    if set(natural) != out_set or len(natural) != len(inds_out):
        raise ValueError(
            f"output {inds_out!r} is not a permutation of batch+M+N "
            f"{natural!r}"
        )
    nat_pos = {ix: i for i, ix in enumerate(natural)}
    out_perm = tuple(nat_pos[ix] for ix in inds_out)

    from ..core.executor import einsum_expr  # shared labeling convention

    try:
        expr = einsum_expr(inds_a, inds_b, inds_out)
    except IndexError:
        # more distinct indices than einsum subscript letters — only
        # possible on paper-scale planning-only nodes (>= 2^52 FLOPs),
        # which the refiner always routes to GEMM backends; the einsum
        # fallback string is never consulted for them.
        expr = ""
    return GemmForm(
        inds_a=tuple(inds_a),
        inds_b=tuple(inds_b),
        inds_out=tuple(inds_out),
        batch_inds=batch,
        m_inds=m_inds,
        n_inds=n_inds,
        k_inds=k_inds,
        perm_a=perm_a,
        perm_b=perm_b,
        out_perm=out_perm,
        batch_shape=tuple(size_of(ix) for ix in batch),
        m_shape=tuple(size_of(ix) for ix in m_inds),
        n_shape=tuple(size_of(ix) for ix in n_inds),
        k_shape=tuple(size_of(ix) for ix in k_inds),
        expr=expr,
    )


def contract_flat(spec, ds, a: jax.Array, b: jax.Array, *,
                  interpret: bool | None = None) -> jax.Array:
    """Execute one step on flat operands (the executor's storage form).

    ``ds`` is the step's :class:`~repro.lowering.layout.DenseStep`:
    each operand is permuted from its storage order into its GEMM order
    through lane-dense transposes, the GEMM runs on 3-D ``(B, ·, ·)``
    operands, and the flat result is in ``ds.out_order``.  ``spec`` (a
    refiner ``GemmSpec``; ``None`` on the einsum backend) picks the
    Pallas kernel for MXU-sized steps; every other step is one XLA
    ``dot_general``.  fp32 steps ask for ``Precision.HIGHEST`` — on a
    TPU the default f32 matmul rounds its inputs to bf16.

    The operand permutes run under ``jax.named_scope("permute")`` and
    the GEMM (with the Karatsuba sums and differences) under ``"gemm"``,
    so a profile's device ops name their part of the step.

    Trace-safe: shapes and the backend choice are static, so this runs
    unchanged under ``jit``, the executor's slice-batch ``vmap``, and
    ``shard_map``."""
    from .layout import permute_flat  # lazy: avoid cycle

    with jax.named_scope("permute"):
        a3 = permute_flat(a, ds.a_order, ds.a_gemm, ds.size_of)
        b3 = permute_flat(b, ds.b_order, ds.b_gemm, ds.size_of)
        a3 = a3.reshape(ds.a_shape)
        b3 = b3.reshape(ds.b_shape)
    real_bytes = real_component_bytes(jnp.result_type(a.dtype, b.dtype))
    precision = spec.precision if spec is not None else "fp32"
    with jax.named_scope("gemm"):
        # 64-bit components would be silently truncated by the fp32
        # Pallas accumulator: they stay on XLA's dot
        if spec is not None and spec.backend == "pallas" and real_bytes <= 4:
            from ..kernels import ops

            mm = functools.partial(
                ops.matmul, bm=spec.bm, bn=spec.bn, bk=spec.bk,
                interpret=interpret,
                min_kernel_dim=1,  # the refiner already gated tiny shapes
                precision=precision,
            )
            if ds.a_shape[0] > 1:
                out = jax.vmap(mm)(a3, b3)
            else:
                out = mm(a3[0], b3[0])[None]
        else:
            lhs, rhs = (b3, a3) if ds.swap else (a3, b3)
            out = jax.lax.dot_general(
                lhs, rhs, ds.dims, precision=jax.lax.Precision.HIGHEST
            )
        return out.reshape(-1)


def apply(spec, a: jax.Array, b: jax.Array, *, interpret: bool | None = None):
    """Execute one refined step (``spec`` is a refiner ``GemmSpec``) on
    operands with one axis per index, in ``spec.form``'s orders; returns
    the output in ``inds_out`` order.  Runs :func:`contract_flat`."""
    from .layout import dense_step, permute_flat  # lazy: avoid cycle

    form: GemmForm = spec.form
    size = dict(zip(form.batch_inds, form.batch_shape))
    size.update(zip(form.m_inds, form.m_shape))
    size.update(zip(form.n_inds, form.n_shape))
    size.update(zip(form.k_inds, form.k_shape))
    ds = dense_step(
        form.inds_a, form.inds_b, form.inds_out, size.__getitem__,
        canonical=spec.backend == "pallas",
    )
    out = contract_flat(
        spec, ds, a.reshape(-1), b.reshape(-1), interpret=interpret
    )
    out = permute_flat(out, ds.out_order, form.inds_out, size.__getitem__)
    return out.reshape(tuple(size[ix] for ix in form.inds_out))
