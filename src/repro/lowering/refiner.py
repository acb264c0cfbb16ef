"""Adaptive tile refiner — the paper's Sec. V-B path refiner mapped to TPU.

On Sunway the refiner permutes/splits contraction indices until every
stem GEMM matches the SWTT fused-kernel tile requirements (8×8 kernels,
DMA-bandwidth roofline).  The TPU analogue implemented here makes three
per-node decisions over the normalized :class:`~repro.lowering.gemm_form.
GemmForm` of every contraction step:

  1. **backend** — Pallas ``tiled_matmul`` for MXU-sized GEMMs,
     ``jnp.dot`` (XLA batched dot_general) for sub-tile shapes where
     kernel padding would dominate, plain ``jnp.einsum`` for tiny or
     degenerate nodes where even the transpose/reshape plumbing costs
     more than the contraction;
  2. **block shapes** — (bm, bn, bk) snapped to multiples of the 128-wide
     MXU tile, chosen per node from a candidate ladder under the VMEM
     residency budget;
  3. **grid steps vs padding** — the Pallas kernel's time is its grid
     steps ``B·⌈M/bm⌉·⌈N/bn⌉·⌈K/bk⌉`` (× Karatsuba's 3) times a per-step
     price: the larger of the tile's MXU time and the DMA of its A and B
     tiles (so the re-reads of A across N and of B across M are
     charged), plus a fixed pipeline cost per step; the output block is
     written once per ``(i, j)``.  Padded tiles count as whole tiles.
     No candidate may pad a dimension past its 128-wide MXU tile: on the
     chip fp32 ``HIGHEST`` takes several MXU passes, which the model does
     not price yet, so a padded row costs more than the model shows.
     Among the rest the lowest modeled time wins, ties going to fewer
     grid steps.

The same per-node cost model (grid steps for Pallas, exact FLOPs capped
by the HBM roofline for dot/einsum, complex traffic counted as
Karatsuba's 3 real GEMMs or the naive 4) is summed
into ``LoweredSchedule.modeled_time_s``, which the API layer feeds back
into ``PlanReport.modeled_time_s`` so planner metrics reflect the
schedule that will actually execute.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Callable, Hashable, Sequence

import jax.numpy as jnp

from ..core.merging import TPU_HBM_BW, TPU_MXU, TPU_PEAK_FLOPS
from .gemm_form import GemmForm, lower_step, real_component_bytes

# candidate Pallas block edges (multiples of the MXU tile)
BLOCK_CANDIDATES = (128, 256, 512)
# VMEM residency budget for one (bm×bk + bk×bn + bm×bn) working set, fp32
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
# below this many real FLOPs a node stays on einsum — the reshape/
# transpose plumbing would cost more than the contraction itself
EINSUM_FLOPS_FLOOR = 2.0 ** 16
# effective peak for non-MXU lowerings (XLA dot_general / einsum on
# sub-tile shapes): mostly VPU + permute work, modeled at peak/8
NON_MXU_PEAK_FRACTION = 0.125
# fixed cost of one Pallas grid step beyond its tile DMA and MXU work:
# the pipeline's per-step overhead, the MXU weight loads and the output
# block's read-modify-write.  On a v5e the fp32 ``tiled_matmul`` at
# 128×128×128 took 0.445 µs per grid step across eleven stem GEMMs from
# K = 256 to K = 65536; its A and B tiles (128 KiB) take 0.160 µs at
# 819 GB/s, so 0.285 µs is left for the step itself.
PALLAS_STEP_S = 0.285e-6


@dataclasses.dataclass(frozen=True)
class GemmSpec:
    """Refined, executable lowering of one contraction step.

    ``transpose_bytes`` is the HBM permute traffic this spec is charged
    (0 on einsum nodes).
    """

    form: GemmForm
    backend: str  # "pallas" | "dot" | "einsum"
    bm: int
    bn: int
    bk: int
    modeled_time_s: float
    pad_waste: float  # fraction of executed MXU FLOPs that are padding
    transpose_bytes: float = 0.0  # HBM bytes moved permuting the operands
    precision: str = "fp32"  # "fp32" | "bf16" (bf16-input/fp32-accumulate)


def precision_itemsize(dtype, precision: str = "fp32") -> int:
    """Storage bytes per element at ``precision``: half the native width
    when the element's real components are held as bf16 (complex64 → a
    bf16 pair = 4 bytes, float32 → 2 bytes), the native width for fp32."""
    itemsize = int(jnp.dtype(dtype).itemsize)
    return max(1, itemsize // 2) if precision == "bf16" else itemsize


def operand_transpose_bytes(
    form: GemmForm, dtype, precision: str = "fp32"
) -> float:
    """HBM traffic of materializing the operand permutations: one read +
    one write per operand whose native layout is not already in GEMM
    order — ``2*(|A|+|B|)*bytes``.
    Operands consumed at bf16 are permuted at their (halved) storage
    width."""
    itemsize = precision_itemsize(dtype, precision)
    t = 0.0
    if form.perm_a != tuple(range(len(form.perm_a))):
        t += 2.0 * itemsize * form.B * form.M * form.K
    if form.perm_b != tuple(range(len(form.perm_b))):
        t += 2.0 * itemsize * form.B * form.K * form.N
    return t


def _ceil_to(x: float, t: int) -> float:
    return max(t, math.ceil(x / t) * t)


def _real_gemm_count(dtype, backend: str) -> int:
    """Real GEMMs per logical GEMM: Karatsuba runs 3, a naive complex
    product runs 4, real dtypes run 1."""
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        return 1
    return 3 if backend == "pallas" else 4


def step_traffic_bytes(
    form: GemmForm, dtype, precision: str = "fp32"
) -> float:
    """Modeled HBM operand + output bytes for one execution of the step
    (excluding any transpose round-trip): inputs at their storage
    precision, output always at the full fp32-component width (the MXU
    accumulates in fp32 and the result is written back as such)."""
    itemsize = int(jnp.dtype(dtype).itemsize)
    in_item = precision_itemsize(dtype, precision)
    return float(form.B) * (
        in_item * (form.M * form.K + form.K * form.N)
        + itemsize * form.M * form.N
    )


def pallas_grid_steps(form: GemmForm, dtype, bm: int, bn: int,
                      bk: int) -> int:
    """Grid steps of one execution of this step on the Pallas kernel:
    ``B·⌈M/bm⌉·⌈N/bn⌉·⌈K/bk⌉`` per real GEMM, times Karatsuba's 3 for
    complex operands."""
    return (
        form.B
        * math.ceil(form.M / bm)
        * math.ceil(form.N / bn)
        * math.ceil(form.K / bk)
        * _real_gemm_count(dtype, "pallas")
    )


def modeled_step_time(
    form: GemmForm,
    dtype,
    backend: str,
    bm: int,
    bn: int,
    bk: int,
    precision: str = "fp32",
) -> tuple[float, float]:
    """(seconds, pad_waste) for one execution of this step.

    Pallas is charged per grid step (:func:`pallas_grid_steps`): the
    larger of the tile's MXU time at full peak and the DMA of its
    ``bm×bk`` A and ``bk×bn`` B tiles, plus :data:`PALLAS_STEP_S`; the
    fp32 output block is written once per ``(i, j)``.  Padded tiles
    count whole.  dot/einsum are charged exact FLOPs at the non-MXU
    effective peak, capped by the HBM roofline on the operand + output
    traffic.  The backends that materialize permuted operand copies
    (``pallas``, ``dot``) additionally pay the ``2*(|A|+|B|)*bytes``
    transpose bandwidth: a separate, non-overlappable HBM round-trip
    before the GEMM proper.

    ``precision="bf16"`` (MXU backends only) doubles the systolic-array
    rate and halves the operand-side traffic — bf16 inputs, fp32
    accumulation, fp32 output writeback.
    """
    n_real = _real_gemm_count(dtype, backend)
    flops = form.flops * n_real
    if backend == "pallas":
        mxu_peak = TPU_PEAK_FLOPS * (2.0 if precision == "bf16" else 1.0)
        ob = 2 if precision == "bf16" else real_component_bytes(dtype)
        steps = pallas_grid_steps(form, dtype, bm, bn, bk)
        t_tile = max(
            2.0 * bm * bn * bk / mxu_peak,
            ob * (bm * bk + bk * bn) / TPU_HBM_BW,
        )
        out_blocks = steps // math.ceil(form.K / bk)
        t = steps * (t_tile + PALLAS_STEP_S) + (
            out_blocks * 4.0 * bm * bn / TPU_HBM_BW
        )
        waste = 1.0 - flops / (2.0 * steps * bm * bn * bk)
    else:
        t_compute = flops / (TPU_PEAK_FLOPS * NON_MXU_PEAK_FRACTION)
        t_mem = step_traffic_bytes(form, dtype, precision) / TPU_HBM_BW
        t = max(t_compute, t_mem)
        waste = 0.0
    if backend in ("pallas", "dot"):
        t += operand_transpose_bytes(form, dtype, precision) / TPU_HBM_BW
    return t, waste


def refine_step(
    form: GemmForm,
    dtype,
    *,
    min_kernel_dim: int = TPU_MXU,
    precision: str = "fp32",
) -> GemmSpec:
    """Pick backend + block shapes for one normalized contraction step.

    ``precision="bf16"`` refines the step under the bf16-input/
    fp32-accumulate model: the VMEM working-set check counts 2-byte
    operand components (the fp32 accumulator tile stays 4-byte), so
    larger blocks become admissible, and the cost model prices 2× MXU
    rate / half operand traffic.  Only MXU backends carry the precision —
    dot/einsum fallbacks always execute fp32.
    """
    real_bytes = real_component_bytes(dtype)
    if form.flops < EINSUM_FLOPS_FLOOR:
        t, w = modeled_step_time(form, dtype, "einsum", 1, 1, 1)
        return GemmSpec(form, "einsum", 0, 0, 0, t, w)
    # 64-bit components (float64 / complex128) would be silently
    # truncated by the fp32 Pallas accumulator — keep them on XLA's dot.
    if min(form.M, form.N, form.K) < min_kernel_dim or real_bytes > 4:
        t, w = modeled_step_time(form, dtype, "dot", 1, 1, 1)
        return GemmSpec(
            form, "dot", 0, 0, 0, t, w, operand_transpose_bytes(form, dtype)
        )
    # per-component operand bytes at the requested precision; the fp32
    # accumulator/output tile is always 4-byte
    ob = 2 if precision == "bf16" else real_bytes
    tbytes = operand_transpose_bytes(form, dtype, precision)
    candidates = []
    for bm, bn, bk in itertools.product(BLOCK_CANDIDATES, repeat=3):
        if ob * (bm * bk + bk * bn) + 4 * bm * bn > VMEM_BUDGET_BYTES:
            continue  # working set must stay VMEM-resident
        if any(
            _ceil_to(d, b) != _ceil_to(d, TPU_MXU)
            for d, b in ((form.M, bm), (form.N, bn), (form.K, bk))
        ):
            continue  # pads past the MXU tile
        t, w = modeled_step_time(form, dtype, "pallas", bm, bn, bk, precision)
        steps = pallas_grid_steps(form, dtype, bm, bn, bk)
        candidates.append(((t, steps), GemmSpec(
            form, "pallas", bm, bn, bk, t, w, tbytes, precision
        )))
    return min(candidates, key=lambda c: c[0])[1]


@dataclasses.dataclass
class LoweredSchedule:
    """Refined kernel schedule for every step of a ContractionPlan.

    ``precision_mode``/``fidelity_tol``/``predicted_amp_error`` record
    the mixed-precision assignment (see :mod:`repro.lowering.precision`):
    the mode the plan was built under, the XEB-fidelity budget it was
    certified against, and the forward error model's accumulated relative
    amplitude error over the bf16 nodes.  All default to the pure-fp32
    schedule."""

    specs: list[GemmSpec]
    dtype: str
    precision_mode: str = "fp32"
    fidelity_tol: float = 0.0
    predicted_amp_error: float = 0.0

    @property
    def modeled_time_s(self) -> float:
        """Modeled seconds for one slice (sum over steps)."""
        return sum(s.modeled_time_s for s in self.specs)

    def backend_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for s in self.specs:
            counts[s.backend] = counts.get(s.backend, 0) + 1
        return counts

    def precision_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for s in self.specs:
            counts[s.precision] = counts.get(s.precision, 0) + 1
        return counts

    def hbm_traffic_bytes(self) -> float:
        """Modeled HBM operand/output traffic for one slice, at each
        step's storage precision, including the materialized-transpose
        round trips (complex elements count both components via the
        native itemsize)."""
        return sum(
            step_traffic_bytes(s.form, self.dtype, s.precision)
            + s.transpose_bytes
            for s in self.specs
        )

    def pad_waste(self) -> float:
        """FLOPs-weighted padding fraction across the Pallas nodes."""
        useful = padded = 0.0
        for s in self.specs:
            if s.backend != "pallas":
                continue
            f = s.form.flops
            useful += f
            padded += f / (1.0 - s.pad_waste) if s.pad_waste < 1.0 else f
        return 0.0 if padded == 0.0 else 1.0 - useful / padded

    def transpose_bytes(self) -> float:
        """HBM bytes this schedule spends materializing operand
        permutations (per slice) — zero on einsum nodes."""
        return sum(s.transpose_bytes for s in self.specs)

    def pallas_grid_steps(self) -> int:
        """Pallas grid steps per slice, over every Pallas step (with
        Karatsuba's 3 real GEMMs for complex steps)."""
        return sum(
            pallas_grid_steps(s.form, self.dtype, s.bm, s.bn, s.bk)
            for s in self.specs
            if s.backend == "pallas"
        )

    def pallas_tiles(self) -> dict[str, int]:
        """Pallas steps by block shape: ``{"bm×bn×bk": count}``."""
        counts: dict[str, int] = {}
        for s in self.specs:
            if s.backend == "pallas":
                key = f"{s.bm}×{s.bn}×{s.bk}"
                counts[key] = counts.get(key, 0) + 1
        return counts

    def summary(self) -> dict:
        return {
            "nodes": len(self.specs),
            "backends": self.backend_counts(),
            "pad_waste": self.pad_waste(),
            "modeled_time_s": self.modeled_time_s,
            "transpose_bytes": self.transpose_bytes(),
            "pallas_grid_steps": self.pallas_grid_steps(),
            "pallas_tiles": self.pallas_tiles(),
            "dtype": self.dtype,
            "precision_mode": self.precision_mode,
            "precision_counts": self.precision_counts(),
            "predicted_amp_error": self.predicted_amp_error,
            "fidelity_tol": self.fidelity_tol,
        }

    def summary_row(self) -> str:
        c = self.backend_counts()
        per = " ".join(
            f"{k}={c[k]}"
            for k in ("pallas", "dot", "einsum")
            if k in c
        )
        pc = self.precision_counts()
        prec = (
            f" bf16={pc['bf16']}/{len(self.specs)}"
            f" amp_err={self.predicted_amp_error:.2e}"
            if pc.get("bf16")
            else ""
        )
        tiles = ",".join(
            f"{k}:{v}" for k, v in sorted(self.pallas_tiles().items())
        )
        grid = (
            f"grid_steps={self.pallas_grid_steps()} tiles={tiles} "
            if tiles else ""
        )
        return (
            f"lowered[{self.dtype}]: {len(self.specs)} nodes ({per}) "
            f"pad_waste={self.pad_waste()*100:.1f}% {grid}"
            f"t_model={self.modeled_time_s:.3e}s/slice{prec}"
        )


def refine_schedule(
    steps: Sequence[tuple[Sequence, Sequence, Sequence]],
    size_of: Callable[[Hashable], int],
    dtype=jnp.complex64,
    *,
    min_kernel_dim: int = TPU_MXU,
) -> LoweredSchedule:
    """Lower + refine every ``(inds_a, inds_b, inds_out)`` step."""
    specs = [
        refine_step(
            lower_step(ia, ib, io, size_of), dtype,
            min_kernel_dim=min_kernel_dim,
        )
        for ia, ib, io in steps
    ]
    return LoweredSchedule(specs, str(jnp.dtype(dtype)))


def refine_tree_schedule(
    tree,
    smask: int = 0,
    dtype=jnp.complex64,
    *,
    min_kernel_dim: int = TPU_MXU,
) -> LoweredSchedule:
    """Refine the kernel schedule for every step of ``(tree, S)``
    directly from the contraction tree — planner-side usage (modeled
    benchmarks, cost projections) on instances too large to instantiate
    an executor plan for.  Mirrors the executor's step construction:
    sliced indices are fixed before lowering, the output index order
    follows ``pair_contract_inds``."""
    from ..core.executor import pair_contract_inds  # lazy: avoid cycle
    from ..core.tensor_network import bits

    space = tree.tn.space
    sliced_labels = {space.labels[b] for b in bits(smask)}
    open_set = frozenset(tree.tn.open_inds)
    node_inds = {
        i: tuple(ix for ix in tree.tn.inputs[i] if ix not in sliced_labels)
        for i in range(tree.tn.num_tensors)
    }
    steps = []
    for v in tree.contract_order():
        l, r = tree.children[v]
        _, out = pair_contract_inds(node_inds[l], node_inds[r], open_set)
        steps.append((node_inds[l], node_inds[r], out))
        node_inds[v] = out
    return refine_schedule(
        steps, tree.tn.size_of, dtype=dtype,
        min_kernel_dim=min_kernel_dim,
    )


def modeled_plan_time(
    tree,
    smask: int = 0,
    dtype=jnp.complex64,
    *,
    part=None,
    precision: str = "fp32",
    fidelity_tol: float | None = None,
) -> float:
    """Modeled wall seconds of *two-phase* execution for ``(tree, S)``:
    the refined prologue runs once, the refined epilogue ``2^|S|`` times.

    Objective evaluation without full plan compilation — no
    ``ContractionPlan`` (and no jit trace) is built, so the anytime
    co-optimizer can score candidates with ``objective="modeled_time"``
    directly from planner state.  ``part`` reuses a caller-held
    :class:`~repro.lowering.partition.TreePartition`.  ``precision``/
    ``fidelity_tol`` score with the mixed-precision assignment the plan
    would actually run under (see :mod:`repro.lowering.precision`)."""
    from ..core.tensor_network import popcount  # lazy: avoid cycle

    sched = refine_tree_schedule(tree, smask, dtype=dtype)
    if not smask:
        if precision != "fp32":
            from .precision import assign_precision  # lazy: avoid cycle

            sched = assign_precision(
                sched, mode=precision, fidelity_tol=fidelity_tol,
            )
        return sched.modeled_time_s
    if part is None:
        from .partition import partition_tree  # lazy: avoid cycle

        part = partition_tree(tree, smask)
    invariant = set(part.invariant_nodes)
    order = tree.contract_order()
    n_slices = 1 << popcount(smask)
    if precision != "fp32":
        from .precision import assign_precision  # lazy: avoid cycle

        epilogue = tuple(
            i for i, v in enumerate(order) if v not in invariant
        )
        sched = assign_precision(
            sched, mode=precision, fidelity_tol=fidelity_tol,
            epilogue_positions=epilogue, n_slices=n_slices,
        )
    prologue_t = sum(
        spec.modeled_time_s
        for v, spec in zip(order, sched.specs)
        if v in invariant
    )
    return prologue_t + (sched.modeled_time_s - prologue_t) * n_slices
