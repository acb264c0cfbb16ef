"""Mixed-precision planning under an XEB error budget — Sec. VI's
single-precision leg mapped to TPU bf16.

The paper's 308.6 Pflops headline is single-precision: the Sunway
kernels compute in reduced precision and accumulate wide, and Huang et
al. (arXiv 2005.06787) show such "frugal" precision is admissible for
supremacy-circuit simulation whenever the induced amplitude error stays
within the XEB fidelity the experiment already sacrifices.  The TPU
analogue here demotes individual contraction steps to
bf16-input/fp32-accumulate ("bf16" on :class:`~repro.lowering.refiner.
GemmSpec`) under a forward error model, certified against a user-set
Linear-XEB fidelity tolerance:

**Error model.**  Rounding a GEMM's operands to bf16 perturbs every
product by at most ``2u`` relative (``u = 2^-9``, 8-bit mantissa,
round-to-nearest).  For random-circuit tensors the component phases are
Porter-Thomas-random, so the K-term accumulation grows like ``sqrt(K)``
against perturbations that also add in quadrature — the *relative*
per-node error stays ~``2u``, with a slowly growing guard for the
correlated tail (``log2 K``) and for the contractions the error still
passes through on the way to the root (``depth``).  Node errors are
independent roundings, so the plan-level relative amplitude error is
their quadrature sum, and the induced Linear-XEB fidelity loss is
``≈ 2×`` that (XEB is quadratic in the amplitudes).

**Assignment.**  Candidates (MXU-backed steps) are ranked by modeled
time saved — epilogue steps weighted by the ``2^|S|`` slice count — per
unit of error, then admitted as a strict prefix while the accumulated
fidelity loss stays within ``fidelity_tol``.  The prefix rule (stop at
the first failure, never skip) makes the assignment monotone in the
tolerance: a smaller ``fidelity_tol`` always selects a subset, and
``fidelity_tol=0`` selects nothing — reproducing the fp32 plan
bitwise.
"""

from __future__ import annotations

import math
import os

import jax.numpy as jnp

from ..core.merging import TPU_MXU
from .gemm_form import GemmForm
from .refiner import GemmSpec, LoweredSchedule, refine_step

PRECISION_MODES = ("fp32", "bf16", "auto")
# bf16 unit roundoff: 8 mantissa bits, round-to-nearest
BF16_UNIT_ROUNDOFF = 2.0 ** -9
# realistic budget: supremacy experiments run at XEB fidelity ~2e-3, so
# a few percent of *relative* fidelity loss disappears into the noise
# floor (Huang et al., arXiv 2005.06787)
DEFAULT_FIDELITY_TOL = 0.05
# backends that execute on the MXU with an fp32 accumulator — the only
# ones that can take bf16 operands
MXU_BACKENDS = ("pallas",)


def default_precision() -> str:
    """Plan-wide precision mode: the ``REPRO_PRECISION`` environment
    variable (CI runs the tier-1 gate under fp32 and auto), defaulting
    to fp32.  ``auto`` demotes steps to bf16 under the XEB error budget;
    ``bf16`` forces every eligible step down regardless of tolerance."""
    v = os.environ.get("REPRO_PRECISION", "fp32")
    if v not in PRECISION_MODES:
        raise ValueError(
            f"REPRO_PRECISION={v!r} not in {PRECISION_MODES}"
        )
    return v


def node_amp_error(form: GemmForm, depth: int = 0) -> float:
    """Relative amplitude error contributed by running one GEMM with
    bf16 inputs (fp32 accumulation): ``2u`` input quantization with a
    guard for the correlated tail of the K-term sum and for the
    ``depth`` contractions the rounded values still pass through."""
    K = max(int(form.K), 1)
    guard = math.sqrt(1.0 + math.log2(K) / 8.0 + depth / 64.0)
    return 2.0 * BF16_UNIT_ROUNDOFF * guard


def predicted_fidelity_loss(amp_error: float) -> float:
    """Linear-XEB fidelity loss induced by a relative amplitude error:
    XEB averages ``|a|^2``, so first order in the perturbation is 2×."""
    return 2.0 * amp_error


def assign_precision(
    schedule: LoweredSchedule,
    *,
    mode: str | None = None,
    fidelity_tol: float | None = None,
    epilogue_positions=None,
    n_slices: int = 1,
    min_kernel_dim: int = TPU_MXU,
) -> LoweredSchedule:
    """Demote schedule steps to bf16 under the XEB error budget.

    Returns a new :class:`LoweredSchedule` whose selected specs were
    re-refined at ``precision="bf16"`` (block shapes re-chosen under the
    halved operand bytes) and whose ``precision_mode``/``fidelity_tol``/
    ``predicted_amp_error`` record the certification.  ``mode="fp32"``
    — or ``"auto"`` with a zero tolerance — returns the input specs
    untouched, so the fp32 plan is reproduced bitwise.

    ``epilogue_positions``/``n_slices`` weight each step's modeled
    saving by how often it executes (the epilogue runs once per slice),
    which orders the greedy admission; membership is then the longest
    prefix whose accumulated fidelity loss stays within tolerance."""
    mode = default_precision() if mode is None else mode
    if mode not in PRECISION_MODES:
        raise ValueError(f"precision={mode!r} not in {PRECISION_MODES}")
    tol = (
        DEFAULT_FIDELITY_TOL if fidelity_tol is None else float(fidelity_tol)
    )
    specs = list(schedule.specs)
    out = lambda sel, err: LoweredSchedule(  # noqa: E731
        sel, schedule.dtype, precision_mode=mode, fidelity_tol=tol,
        predicted_amp_error=err,
    )
    if mode == "fp32" or (mode == "auto" and tol <= 0.0):
        return out(specs, 0.0)
    epi = set(epilogue_positions) if epilogue_positions is not None else None
    n_steps = len(specs)
    candidates = []
    for p, spec in enumerate(specs):
        if spec.backend not in MXU_BACKENDS or spec.precision == "bf16":
            continue
        spec16 = refine_step(
            spec.form, schedule.dtype, min_kernel_dim=min_kernel_dim,
            precision="bf16",
        )
        if spec16.backend not in MXU_BACKENDS:
            continue
        weight = n_slices if (epi is None or p in epi) else 1
        benefit = (spec.modeled_time_s - spec16.modeled_time_s) * weight
        err = node_amp_error(spec.form, depth=n_steps - 1 - p)
        if mode == "auto" and benefit <= 0.0:
            continue
        candidates.append((benefit / err, p, spec16, err))
    err_sq = 0.0
    if mode == "bf16":
        for _, p, spec16, err in candidates:
            specs[p] = spec16
            err_sq += err * err
        return out(specs, math.sqrt(err_sq))
    # auto: benefit-per-error order, strict-prefix admission — stop at
    # the first candidate the budget rejects (monotone in tol)
    candidates.sort(key=lambda c: (-c[0], c[1]))
    for _, p, spec16, err in candidates:
        trial = err_sq + err * err
        if predicted_fidelity_loss(math.sqrt(trial)) > tol:
            break
        specs[p] = spec16
        err_sq = trial
    return out(specs, math.sqrt(err_sq))


def storage_itemsizes(
    step_nodes, specs, dtype, node_ids
) -> dict[int, int]:
    """Per-node *storage* itemsize under a mixed-precision schedule: a
    node is held as bf16 component pairs (half the native width) exactly
    when every GEMM that consumes it reads bf16 operands — rounding at
    the store is then identical to rounding at every consumption, so
    storage precision never changes the numerics.  Unconsumed nodes (the
    root / hoisted frontier outputs) stay full width."""
    full = int(jnp.dtype(dtype).itemsize)
    half = max(1, full // 2)
    consumers: dict[int, list[str]] = {}
    for (lhs, rhs, _out), spec in zip(step_nodes, specs):
        consumers.setdefault(lhs, []).append(spec.precision)
        consumers.setdefault(rhs, []).append(spec.precision)
    return {
        v: half
        if consumers.get(v) and all(p == "bf16" for p in consumers[v])
        else full
        for v in node_ids
    }


def tree_storage_itemsizes(
    tree,
    smask: int = 0,
    *,
    itemsize: int = 8,
    mode: str | None = None,
    fidelity_tol: float | None = None,
) -> dict[int, int] | None:
    """Planner-side storage-itemsize map for ``(tree, S)`` — what
    :func:`~repro.core.slicing.refine_slices_for_peak` needs to certify
    dtype-true peaks before any executor plan exists.  Returns ``None``
    when the assignment selects no bf16 nodes (including fp32 mode and
    itemsizes with no bf16 mapping)."""
    from ..core.tensor_network import popcount  # lazy: avoid cycle
    from .refiner import refine_tree_schedule

    dtype = {8: "complex64", 4: "float32"}.get(int(itemsize))
    if dtype is None:
        return None
    mode = default_precision() if mode is None else mode
    if mode == "fp32":
        return None
    sched = refine_tree_schedule(tree, smask, dtype=dtype)
    order = tree.contract_order()
    epilogue = None
    n_slices = 1
    if smask:
        from .partition import partition_tree  # lazy: avoid cycle

        invariant = set(partition_tree(tree, smask).invariant_nodes)
        epilogue = tuple(
            i for i, v in enumerate(order) if v not in invariant
        )
        n_slices = 1 << popcount(smask)
    sched = assign_precision(
        sched, mode=mode, fidelity_tol=fidelity_tol,
        epilogue_positions=epilogue, n_slices=n_slices,
    )
    if not sched.precision_counts().get("bf16"):
        return None
    step_nodes = tuple((*tree.children[v], v) for v in order)
    return storage_itemsizes(step_nodes, sched.specs, dtype, tree.emask)
