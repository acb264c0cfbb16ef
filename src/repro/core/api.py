"""End-to-end pipeline: circuit → network → path → slicing → tuning →
merging → lowering → sliced JAX contraction.  This is the public API the
examples and benchmarks drive.

Every planner and lowering parameter is a field of :class:`PlanOptions`.
The entry points take those fields as keyword arguments and resolve them
once.  Planned artifacts are memoized in the compiled-plan cache
(:data:`repro.lowering.cache.PLAN_CACHE`) keyed by the canonical network
fingerprint + the resolved options, so repeated requests for the same
circuit family skip planning and retracing — pass ``use_cache=False``
to force a fresh plan.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from ..obs import trace as _trace
from .contraction_tree import ContractionTree
from .executor import (
    BACKENDS,
    ContractionPlan,
    auto_slice_batch,
    default_backend,
    default_hoist,
    simplify_network,
)
from .merging import modeled_tree_time
from .tensor_network import popcount

_SLICING_MODES = ("width", "peak")
_OPTIMIZERS = ("oneshot", "anytime")
# the anytime search's budgets: they shape a plan only under
# optimize="anytime", so only there do they join the cache key
_SEARCH_FIELDS = ("search_evals", "search_workers", "search_wall_s")


@dataclasses.dataclass(frozen=True)
class PlanOptions:
    """Every planner and lowering parameter, declared once.

    :func:`plan_contraction`, :func:`plan_compiled`,
    :func:`simulate_amplitude`, :func:`open_amplitude_batch`,
    :func:`sample_bitstrings` and :func:`open_session` take these fields
    as keyword arguments; an unknown name raises :class:`TypeError`.
    :meth:`resolve` fills the environment defaults and checks the values
    before any planning.

    The plan-cache key (:meth:`key`) holds every resolved field but two
    kinds, so that knobs a plan ignores cannot split the cache: the
    search budgets count only under ``optimize="anytime"``, and
    ``fidelity_tol`` only away from ``precision="fp32"``.
    """

    #: ``"einsum"`` runs every node as ``jnp.einsum`` (the oracle path);
    #: ``"gemm"`` lowers the tree through :mod:`repro.lowering` into an
    #: explicit kernel schedule (Pallas tiled GEMMs + refined fallbacks).
    #: ``None`` follows ``REPRO_BACKEND``, default einsum.
    backend: str | None = None
    #: slicer: ``"lifetime"`` (Alg. 1) or another
    #: :func:`repro.core.slicing.find_slices` method
    method: str = "lifetime"
    #: branch exchange + tuningSliceFinder (Alg. 2), lifetime slicer only
    tune: bool = True
    #: branch merging under the GEMM F(M,N,K) surface (Sec. V)
    merge: bool = True
    #: restarts of the randomized greedy path search
    repeats: int = 8
    #: seed of the path search and the randomized slicers
    seed: int = 0
    #: ``"width"`` slices until every tensor fits ``2^target_dim``;
    #: ``"peak"`` then re-judges the mask against the lifetime memory
    #: plan's live-set peak (:func:`repro.core.slicing.refine_slices_for_peak`)
    #: and drops the indices the true peak never needed.
    slicing_mode: str = "width"
    #: ``"oneshot"`` runs the staged pipeline, each stage once
    #: (:func:`repro.optimize.oneshot_plan`).  ``"anytime"`` runs the
    #: path–slice–memory co-optimizer (:func:`repro.optimize.plan_search`):
    #: the slicer re-runs after every accepted tree move, candidates are
    #: scored by hoist-aware executed FLOPs under the certified peak
    #: budget, and the report carries the improvement trace.
    optimize: str = "oneshot"
    #: the anytime budgets: candidate evaluations, annealing workers and
    #: an optional wall clock.  Stopping early still yields a plan no
    #: worse than the one-shot seed; a wall-clock budget makes the plan
    #: depend on the machine.
    search_evals: int = 64
    search_workers: int = 4
    search_wall_s: float | None = None
    #: certified live-set byte budget of peak slicing and the search;
    #: ``None`` derives it from ``target_dim``
    budget_bytes: int | None = None
    #: ``"fp32"``; ``"auto"`` demotes MXU GEMM steps to bf16-input /
    #: fp32-accumulate while the forward error model keeps the predicted
    #: Linear-XEB loss within ``fidelity_tol``; ``"bf16"`` demotes every
    #: eligible step.  ``None`` follows ``REPRO_PRECISION``, default fp32.
    precision: str | None = None
    #: the relative Linear-XEB loss ``"auto"`` may spend; ``None`` is
    #: :data:`repro.lowering.precision.DEFAULT_FIDELITY_TOL`
    fidelity_tol: float | None = None

    def resolve(self) -> PlanOptions:
        """These options with the environment defaults filled in, after
        checking every field that names a mode."""
        from ..lowering.precision import (  # lazy: avoid cycle
            DEFAULT_FIDELITY_TOL,
            PRECISION_MODES,
            default_precision,
        )

        backend, precision, tol = (
            self.backend, self.precision, self.fidelity_tol
        )
        opts = dataclasses.replace(
            self,
            backend=default_backend() if backend is None else backend,
            precision=default_precision() if precision is None else precision,
            fidelity_tol=DEFAULT_FIDELITY_TOL if tol is None else float(tol),
        )
        for name, allowed in (
            ("backend", BACKENDS),
            ("precision", PRECISION_MODES),
            ("slicing_mode", _SLICING_MODES),
            ("optimize", _OPTIMIZERS),
        ):
            value = getattr(opts, name)
            if value not in allowed:
                raise ValueError(f"{name} {value!r} not in {allowed}")
        return opts

    def key(self) -> tuple:
        """The resolved options as they join the plan-cache key."""
        ignored = set()
        if self.optimize != "anytime":
            ignored.update(_SEARCH_FIELDS)
        if self.precision == "fp32":
            ignored.add("fidelity_tol")
        return tuple(
            (f.name, getattr(self, f.name))
            for f in dataclasses.fields(self)
            if f.name not in ignored
        )


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if b < 1024:
            return f"{b:.0f}{unit}"
        b /= 1024
    return f"{b:.1f}TB"


@dataclasses.dataclass
class PlanReport:
    """Planner metrics mirroring the paper's reported quantities."""

    num_tensors: int
    width_before: int
    width_after: int
    log2_cost: float
    log2_sliced_cost: float
    num_sliced: int
    slicing_overhead: float  # Eq. 4
    modeled_time_s: float  # Sec. V model, one chip
    plan_wall_s: float
    # execution backend + lowering/cache metrics (PR 2)
    backend: str = "einsum"
    cache_hit: bool = False
    cache_hits: int = 0
    cache_misses: int = 0
    lowered_backends: dict | None = None  # node counts per kernel backend
    pad_waste: float = 0.0  # FLOPs-weighted MXU padding fraction
    # two-phase (lifetime-partitioned) execution metrics (PR 3)
    hoist: bool = True  # whether two-phase execution is enabled
    invariant_fraction: float = 0.0  # share of C(B) hoisted out of slices
    measured_overhead: float = 1.0  # executed-FLOPs overhead of the mode
    modeled_time_hoisted_s: float = 0.0  # Sec. V model under hoisting
    # lifetime-based memory plan metrics (PR 4)
    peak_bytes: int = 0  # exact live-set peak, naive subtask
    peak_bytes_hoisted: int = 0  # live-set peak under two-phase execution
    buffer_slots: int = 0  # linear-scan slot count (naive subtask)
    # anytime path–slice co-optimizer metrics (PR 5)
    optimize: str = "oneshot"  # planner mode: oneshot | anytime
    search_evals: int = 0  # candidate evaluations the search spent
    search_trace: list | None = None  # best-so-far improvements (dicts)
    # observability (PR 7): metrics snapshot + per-span aggregates from
    # repro.obs.telemetry_summary(), populated only when tracing is on
    # (REPRO_TRACE=1 or the telemetry= toggle) — None otherwise
    telemetry: dict | None = None
    # multi-host scheduling (PR 8): realized max/mean host load, ranges
    # stolen across hosts, and the fraction of the reduction hidden
    # behind slice compute — populated by contract_multihost when a
    # report is threaded through; defaults describe a single-host run
    schedule_imbalance: float = 0.0  # 0.0 = not a multi-host run
    steal_count: int = 0
    overlap_fraction: float = 0.0
    # mixed precision under an XEB error budget (PR 9)
    precision: str = "fp32"  # resolved mode: fp32 | bf16 | auto
    fidelity_tol: float = 0.0  # the XEB budget the plan was certified at
    precision_counts: dict | None = None  # GEMM-step counts per precision
    predicted_amp_error: float = 0.0  # forward-model relative amp error
    # lane-dense store orders (lowering/layout.store_layout): modeled
    # HBM bytes of one pass through the tree's permutes, for the chosen
    # orders and the greedy ones, and the steps whose output is stored
    # in the order its consumer asked for
    permute_bytes: int = 0
    permute_bytes_greedy: int = 0
    lookahead_orders: int = 0

    def row(self) -> str:
        row = (
            f"tensors={self.num_tensors} W={self.width_before}->"
            f"{self.width_after} log2C={self.log2_cost:.2f} "
            f"slices={self.num_sliced} overhead={self.slicing_overhead:.3f} "
            f"t_model={self.modeled_time_s:.3e}s plan={self.plan_wall_s:.2f}s "
            f"backend={self.backend}"
        )
        if self.num_sliced:
            row += (
                f" hoist={'on' if self.hoist else 'off'}"
                f"[inv={self.invariant_fraction:.2f}"
                f" ov={self.measured_overhead:.3f}]"
            )
        if self.optimize != "oneshot":
            row += f" opt={self.optimize}[evals={self.search_evals}]"
        if self.peak_bytes:
            row += f" peak={_fmt_bytes(self.peak_bytes)}"
            if self.peak_bytes_hoisted != self.peak_bytes:
                row += f"->{_fmt_bytes(self.peak_bytes_hoisted)}"
            row += f" slots={self.buffer_slots}"
        if self.permute_bytes_greedy:
            row += f" permute={_fmt_bytes(self.permute_bytes)}"
            if self.lookahead_orders:
                row += (
                    f"[greedy={_fmt_bytes(self.permute_bytes_greedy)}"
                    f" lookahead={self.lookahead_orders}]"
                )
        if self.cache_hit:
            row += " cache=hit"
        if self.lowered_backends:
            nodes = " ".join(
                f"{k}={v}" for k, v in sorted(self.lowered_backends.items())
            )
            row += f" lowered[{nodes}] pad_waste={self.pad_waste*100:.1f}%"
        if self.schedule_imbalance:
            row += (
                f" sched[imb={self.schedule_imbalance:.2f}"
                f" steals={self.steal_count}"
                f" overlap={self.overlap_fraction:.2f}]"
            )
        if self.precision != "fp32":
            counts = self.precision_counts or {}
            total = sum(counts.values())
            row += (
                f" prec={self.precision}"
                f"[bf16={counts.get('bf16', 0)}/{total}"
                f" tol={self.fidelity_tol:g}"
                f" amp_err={self.predicted_amp_error:.2e}]"
            )
        return row


@dataclasses.dataclass
class SimulationResult:
    value: np.ndarray | complex
    report: PlanReport
    tree: ContractionTree
    smask: int
    plan: ContractionPlan | None = None  # carries the lowered schedule


def _telemetry_snapshot() -> dict:
    from .. import obs  # lazy: obs is also importable standalone

    return obs.telemetry_summary()


def _with_hoist(report: PlanReport, plan, hoist: bool | None) -> PlanReport:
    """``report`` with its hoist fields describing the mode that will run:
    ``hoist``, or ``REPRO_HOIST`` when ``None``.  The mode is chosen at
    execution time, so it is read on every call and never cached."""
    on = default_hoist() if hoist is None else bool(hoist)
    return dataclasses.replace(
        report, hoist=on, measured_overhead=plan.executed_overhead(on)
    )


def plan_contraction(tn, target_dim: int, itemsize: int = 8, **options):
    """Full planning pipeline on a tensor network: returns ``(tree,
    smask, report)``.  ``options`` are :class:`PlanOptions` fields;
    ``itemsize`` is the element width the memory plan counts."""
    return _plan_tree(
        tn, target_dim, itemsize, PlanOptions(**options).resolve()
    )


@_trace.traced("plan.build", cat="plan")
def _plan_tree(tn, target_dim: int, itemsize: int, opts: PlanOptions):
    from ..optimize import oneshot_plan, plan_search

    t0 = time.perf_counter()
    search_trace = None
    if opts.optimize == "anytime":
        sr = plan_search(
            tn, target_dim, budget_bytes=opts.budget_bytes,
            itemsize=itemsize, num_workers=opts.search_workers,
            max_evals=opts.search_evals, wall_clock_s=opts.search_wall_s,
            seed=opts.seed, method=opts.method, tune=opts.tune,
            merge=opts.merge, repeats=opts.repeats,
            slicing_mode=opts.slicing_mode, precision=opts.precision,
            fidelity_tol=opts.fidelity_tol,
        )
        tree, smask = sr.tree, sr.smask
        width0 = sr.width_before  # raw greedy seed width, as in oneshot
        search_trace = [dataclasses.asdict(t) for t in sr.trace]
    else:
        shot = oneshot_plan(
            tn, target_dim, method=opts.method, tune=opts.tune,
            merge=opts.merge, repeats=opts.repeats, seed=opts.seed,
            slicing_mode=opts.slicing_mode, itemsize=itemsize,
            budget_bytes=opts.budget_bytes, precision=opts.precision,
            fidelity_tol=opts.fidelity_tol,
        )
        tree, smask, width0 = shot.tree, shot.smask, shot.width_before
    wall = time.perf_counter() - t0
    naive_overhead = tree.slicing_overhead(smask)
    hoist_on = default_hoist()
    invariant_fraction = 0.0
    hoisted_overhead = naive_overhead
    part = None
    if smask:
        from ..lowering.partition import partition_tree  # lazy: cycle

        part = partition_tree(tree, smask)
        invariant_fraction = part.invariant_fraction
        hoisted_overhead = part.hoisted_overhead()
    modeled = modeled_tree_time(tree, smask)
    from ..lowering.memory import plan_memory  # lazy: avoid cycle

    mem = plan_memory(tree, smask, itemsize=itemsize, part=part)
    report = PlanReport(
        num_tensors=tn.num_tensors,
        width_before=width0,
        width_after=tree.sliced_width(smask),
        log2_cost=tree.log2_total_cost(),
        log2_sliced_cost=math.log2(tree.sliced_cost(smask)),
        num_sliced=popcount(smask),
        slicing_overhead=naive_overhead,
        modeled_time_s=modeled,
        plan_wall_s=wall,
        hoist=hoist_on,
        invariant_fraction=invariant_fraction,
        measured_overhead=hoisted_overhead if hoist_on else naive_overhead,
        modeled_time_hoisted_s=modeled * hoisted_overhead / naive_overhead,
        peak_bytes=mem.peak_bytes,
        peak_bytes_hoisted=mem.peak_bytes_hoisted,
        buffer_slots=mem.buffer_slots,
        optimize=opts.optimize,
        search_evals=sr.evaluations if opts.optimize == "anytime" else 0,
        search_trace=search_trace,
    )
    return tree, smask, report


def plan_compiled(
    tn,
    target_dim: int,
    dtype=None,
    *,
    use_cache: bool = True,
    telemetry: bool | None = None,
    **options,
) -> tuple[ContractionPlan, PlanReport]:
    """Plan + lower a network into an executable :class:`ContractionPlan`,
    consulting the compiled-plan cache.  ``options`` are
    :class:`PlanOptions` fields.

    The cache key is the canonical network fingerprint (structure +
    dtype + open indices, invariant under index relabeling), the target
    width and :meth:`PlanOptions.key`, so a hit returns the *identical*
    plan object — its lowered schedule and memoized jitted executables
    ride along, which is what makes a hit skip retracing, not just
    planning.  The slicing mask ``S`` is part of the cached artifact (it
    is a deterministic function of the key), and so is a search result
    under ``optimize="anytime"``.

    ``telemetry=True`` forces span tracing + metrics on for this call
    (``False`` forces off, ``None`` follows ``REPRO_TRACE``); when
    tracing is on the returned report carries
    ``PlanReport.telemetry`` — the :func:`repro.obs.telemetry_summary`
    snapshot taken after planning.  The toggle never joins the plan
    fingerprint: traced and untraced calls share cache entries and
    produce bitwise-identical plans.
    """
    opts = PlanOptions(**options).resolve()
    with _trace.enabled_scope(telemetry):
        plan, report = _plan_compiled(tn, target_dim, dtype, opts, use_cache)
        if _trace.enabled():
            report = dataclasses.replace(
                report, telemetry=_telemetry_snapshot()
            )
    return plan, report


def _plan_compiled(
    tn, target_dim: int, dtype, opts: PlanOptions, use_cache: bool
) -> tuple[ContractionPlan, PlanReport]:
    from ..lowering.cache import PLAN_CACHE, PlanEntry, network_fingerprint
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype if dtype is not None else jnp.complex64)
    t0 = time.perf_counter()
    if not use_cache:
        plan, report = _plan_fresh(tn, target_dim, dtype, opts, t0)
        return plan, _with_hoist(report, plan, None)
    key = network_fingerprint(tn, dtype, extra=(target_dim, opts.key()))
    fresh: list[PlanEntry] = []

    def _factory() -> PlanEntry:
        ent = PlanEntry(*_plan_fresh(tn, target_dim, dtype, opts, t0))
        fresh.append(ent)
        return ent

    # single-flight: concurrent misses on one family (threaded serving
    # dispatch) elect one planner; the rest wait for its entry instead of
    # replanning — and the get→plan→put race that let two threads each
    # plan and the loser overwrite the winner's jit-warmed plan is gone
    ent = PLAN_CACHE.single_flight(key, _factory)
    stats = PLAN_CACHE.stats()
    report = dataclasses.replace(
        ent.report,
        cache_hits=stats["hits"],
        cache_misses=stats["misses"],
        # copy the one mutable field so a caller mutating its
        # report can never corrupt the cached template
        search_trace=(
            [dict(t) for t in ent.report.search_trace]
            if ent.report.search_trace is not None
            else None
        ),
    )
    if not fresh:
        # cache hit (or waited on another thread's in-flight planning)
        report = dataclasses.replace(
            report, plan_wall_s=time.perf_counter() - t0, cache_hit=True
        )
    return ent.plan, _with_hoist(report, ent.plan, None)


def _plan_fresh(
    tn, target_dim: int, dtype, opts: PlanOptions, t0: float
) -> tuple[ContractionPlan, PlanReport]:
    """One fresh planning + lowering run (no cache consultation) — the
    body a :meth:`PlanCache.single_flight` leader executes."""
    tree, smask, report = _plan_tree(tn, target_dim, dtype.itemsize, opts)
    with _trace.span("plan.lower", cat="plan", backend=opts.backend):
        plan = ContractionPlan(
            tree, smask, backend=opts.backend, dtype=dtype,
            precision=opts.precision, fidelity_tol=opts.fidelity_tol,
        )
    report.backend = plan.backend
    report.precision = plan.precision_mode
    if plan.precision_mode != "fp32":
        report.fidelity_tol = plan.fidelity_tol
    # re-derive the two-phase metrics from the plan's own partition so the
    # report always describes the object that will execute (the memory
    # fields were already computed by _plan_tree with this dtype's
    # itemsize — no recompute needed)
    report.invariant_fraction = plan.invariant_fraction
    report.permute_bytes = plan.permute_bytes
    report.permute_bytes_greedy = plan.permute_bytes_greedy
    report.lookahead_orders = plan.lookahead_orders
    if plan.schedule is not None:
        # refiner feedback: the modeled time now reflects the refined
        # schedule that will actually execute (per-slice × slice count)
        report.modeled_time_s = plan.schedule.modeled_time_s * (
            1 << plan.num_sliced
        )
        # hoisted variant: prologue specs run once, epilogue per slice
        prologue_t = sum(
            plan.schedule.specs[k].modeled_time_s for k in plan.prologue_idx
        )
        report.modeled_time_hoisted_s = prologue_t + (
            plan.schedule.modeled_time_s - prologue_t
        ) * (1 << plan.num_sliced)
        report.lowered_backends = plan.schedule.backend_counts()
        report.pad_waste = plan.schedule.pad_waste()
        report.precision_counts = plan.schedule.precision_counts()
        report.predicted_amp_error = plan.schedule.predicted_amp_error
        if plan._itemsize_of:
            # bf16-stored intermediates shrink the true live-set peak —
            # re-derive the memory fields from the plan's own dtype-true
            # memory plan (plan_contraction counted fp32 storage)
            mem = plan.memory_plan()
            report.peak_bytes = mem.peak_bytes
            report.peak_bytes_hoisted = mem.peak_bytes_hoisted
            report.buffer_slots = mem.buffer_slots
    report.plan_wall_s = time.perf_counter() - t0
    return plan, report


def simulate_amplitude(
    circuit,
    bitstring: str,
    target_dim: int = 20,
    *,
    slice_batch: int = 4,
    hoist: bool | None = None,
    use_cache: bool = True,
    telemetry: bool | None = None,
    **options,
) -> SimulationResult:
    """Amplitude <bitstring|C|0…0> via the full planner + executor stack.

    ``options`` are :class:`PlanOptions` fields.  ``hoist`` selects
    two-phase (slice-invariant hoisted) execution, default
    ``REPRO_HOIST``.  Two calls on the same circuit share one compiled
    plan via the plan cache (different bitstrings change leaf *values*,
    never network structure).
    """
    from ..quantum.circuits import circuit_to_network  # avoid import cycle

    with _trace.enabled_scope(telemetry):
        tn, arrays = circuit_to_network(circuit, bitstring=bitstring)
        tn, arrays = simplify_network(tn, arrays)
        plan, report = plan_compiled(
            tn, target_dim,
            dtype=arrays[0].dtype if arrays else None,
            use_cache=use_cache, **options,
        )
        sb = auto_slice_batch(slice_batch, 1 << plan.num_sliced)
        value = plan.contract_all(arrays, slice_batch=sb, hoist=hoist)
        report = _with_hoist(report, plan, hoist)
        if _trace.enabled():
            report = dataclasses.replace(
                report, telemetry=_telemetry_snapshot()
            )
    return SimulationResult(
        np.asarray(value), report, plan.tree, plan.smask, plan
    )


def sample_bitstrings(
    circuit,
    num_samples: int = 1024,
    open_qubits=None,
    base_bitstring: str | None = None,
    target_dim: int = 20,
    *,
    slice_batch: int = 4,
    sampler: str = "frequency",
    mesh=None,
    axis_names: tuple[str, ...] = ("data",),
    hoist: bool | None = None,
    use_cache: bool = True,
    telemetry: bool | None = None,
    **options,
):
    """Draw correlated bitstring samples from one batched contraction —
    the paper's flagship workload (Sec. VI: 1M correlated Sycamore samples).

    ``open_qubits`` (default: the last ``min(6, n)`` qubits) stay open
    through the contraction stem, so a *single* sliced contraction yields
    all ``2^k`` amplitudes sharing the ``base_bitstring`` prefix (default
    all-zeros).  Bitstrings are then drawn from that batch with the chosen
    ``sampler`` ('frequency' — exact multinomial over |a|², 'rejection' —
    unbiased accept/reject, or 'topk' — heaviest outputs), and the sample
    set is scored with Linear XEB.

    ``options`` are :class:`PlanOptions` fields; ``seed`` seeds the
    sampler as well as the planner.  Pass a jax ``mesh`` to shard the
    slice ids over ``axis_names`` (shard_map + one psum); the open-batch
    axes are replicated so every device returns the full batch.  The
    compiled plan is cached per circuit family like
    :func:`simulate_amplitude`.  Under two-phase execution (``hoist``,
    default ``REPRO_HOIST``) repeated sampler calls on the same batch
    network reuse the hoisted slice-invariant stem via the prologue
    cache.

    Returns a :class:`repro.sampling.SamplingResult`.

    Example::

        from repro.core import sample_bitstrings
        from repro.quantum.circuits import sycamore_like

        res = sample_bitstrings(
            sycamore_like(4, 4, 10), num_samples=1000,
            open_qubits=(12, 13, 14, 15), target_dim=12,
        )
        print(res.bitstrings[:3], res.xeb)
    """
    if num_samples <= 0:
        raise ValueError(f"num_samples must be positive, got {num_samples}")
    if sampler not in ("frequency", "rejection", "topk"):
        raise ValueError(f"unknown sampler {sampler!r}")  # fail pre-contraction

    with _trace.enabled_scope(telemetry):
        batch, report = open_amplitude_batch(
            circuit, open_qubits, base_bitstring, target_dim,
            slice_batch=slice_batch, mesh=mesh, axis_names=axis_names,
            hoist=hoist, use_cache=use_cache, **options,
        )
        res = draw_from_batch(
            batch, num_samples, sampler=sampler,
            seed=options.get("seed", PlanOptions.seed),
        )
        if _trace.enabled():
            report = dataclasses.replace(
                report, telemetry=_telemetry_snapshot()
            )
    res.report = report
    return res


def open_amplitude_batch(
    circuit,
    open_qubits=None,
    base_bitstring: str | None = None,
    target_dim: int = 20,
    *,
    slice_batch: int = 4,
    mesh=None,
    axis_names: tuple[str, ...] = ("data",),
    hoist: bool | None = None,
    use_cache: bool = True,
    **options,
):
    """Contract one open-qubit batch: the planning + execution half of
    :func:`sample_bitstrings`, without drawing any samples.

    Returns ``(AmplitudeBatch, PlanReport)`` — all ``2^k`` correlated
    amplitudes sharing ``base_bitstring`` outside ``open_qubits``.  The
    serving engine (:mod:`repro.engine.server`) calls this directly: one
    batch contraction answers a whole coalesced group of amplitude
    requests (read at their flat batch indices) or feeds any number of
    per-tenant :func:`draw_from_batch` calls.  Defaults mirror
    :func:`sample_bitstrings` (open the last ``min(6, n)`` qubits,
    all-zeros base); ``options`` are :class:`PlanOptions` fields."""
    from ..sampling import AmplitudeBatch, batch as batch_mod

    n = circuit.num_qubits
    if open_qubits is None:
        k = min(6, n)
        open_qubits = tuple(range(n - k, n))
    open_qubits = tuple(sorted(set(open_qubits)))
    if not open_qubits:
        raise ValueError("need at least one open qubit")
    if base_bitstring is None:
        base_bitstring = "0" * n
    elif len(base_bitstring) != n or set(base_bitstring) - {"0", "1"}:
        raise ValueError(
            f"base_bitstring must be {n} chars of 0/1, got {base_bitstring!r}"
        )

    tn, arrays = batch_mod.open_batch_network(
        circuit, base_bitstring, open_qubits
    )
    # open indices cannot be sliced: the width floor is the batch rank
    plan, report = plan_compiled(
        tn, max(target_dim, len(open_qubits) + 1),
        dtype=arrays[0].dtype if arrays else None,
        use_cache=use_cache, **options,
    )
    amps = batch_mod.contract_amplitude_batch(
        plan, arrays, slice_batch=slice_batch, mesh=mesh,
        axis_names=axis_names, hoist=hoist,
    )
    report = _with_hoist(report, plan, hoist)
    return AmplitudeBatch(amps, open_qubits, base_bitstring, n), report


def draw_from_batch(
    batch,
    num_samples: int,
    sampler: str = "frequency",
    seed: int = 0,
    report: PlanReport | None = None,
):
    """Draw + score a sample set from an already-contracted
    :class:`~repro.sampling.AmplitudeBatch`.

    The sampling half of :func:`sample_bitstrings`: many tenants (or
    repeated calls with different seeds/samplers) can share one batch
    contraction and each pay only the multinomial/rejection draw.
    Returns a :class:`~repro.sampling.SamplingResult`."""
    from ..quantum import xeb as xeb_mod  # avoid import cycle
    from ..sampling import samplers

    if num_samples <= 0:
        raise ValueError(f"num_samples must be positive, got {num_samples}")
    if sampler not in ("frequency", "rejection", "topk"):
        raise ValueError(f"unknown sampler {sampler!r}")
    idx = samplers.draw(batch, num_samples, sampler=sampler, seed=seed)
    flat = batch.flat()
    sampled_amps = flat[idx]
    probs = np.abs(sampled_amps) ** 2
    return samplers.SamplingResult(
        bitstrings=batch.bitstrings_for(idx),
        amplitudes=sampled_amps,
        probs=probs,
        xeb=xeb_mod.linear_xeb(batch.num_qubits, probs),
        batch=batch,
        sampler=sampler,
        report=report,
    )


def open_session(
    circuit,
    bitstring: str,
    target_dim: int = 20,
    *,
    hoist: bool | None = None,
    use_cache: bool = True,
    **options,
):
    """Plan a circuit amplitude and return a live
    :class:`~repro.engine.session.ContractionSession` plus its report.

    The session is the engine-level handle the slice drivers share: the
    compiled plan bound to this bitstring's leaf arrays, hoist mode
    resolved, ready for ``run_slice`` / ``run_slices`` / ``run_all``.
    Callers that want to schedule slice execution themselves (custom
    drivers, the serving engine, incremental/resumable loops) start
    here instead of :func:`simulate_amplitude`.  ``options`` are
    :class:`PlanOptions` fields."""
    from ..engine.session import ContractionSession
    from ..quantum.circuits import circuit_to_network  # avoid import cycle

    tn, arrays = circuit_to_network(circuit, bitstring=bitstring)
    tn, arrays = simplify_network(tn, arrays)
    plan, report = plan_compiled(
        tn, target_dim,
        dtype=arrays[0].dtype if arrays else None,
        use_cache=use_cache, **options,
    )
    return ContractionSession(plan, arrays, hoist=hoist), report
