"""Model-vs-measured calibration: per-node wall against the refiner model.

The refiner chooses backends by ``modeled_time_s`` (an F(M,N,K)
efficiency model over GEMM shapes), the slicer trusts
``modeled_node_time`` (Eq. 4 cost algebra at modeled bandwidth), and the
lifetime planner certifies live-set peaks — but until this module nothing
ever *checked* those models against real hardware.  :func:`calibrate_plan`
executes a plan's steps eagerly, one at a time, with a
``block_until_ready`` fence around each, and joins the measured walls
with the modeled per-slice times into a per-backend-class table
(``pallas`` / ``dot`` / ``einsum``; under mixed precision, non-fp32
steps split into their own rows, e.g. ``pallas[bf16]`` — bf16 runs
against a different MXU roofline, so its measured/modeled ratio is a
separate signal).

The measured/modeled ratio per class is the feedback signal the
ROADMAP's adaptive refiner and work-stealing scheduler need: a class
with ratio ≫ 1 means the model flatters that backend and the refiner's
choices are suspect on this machine; ratios drifting apart across
classes mean the crossover thresholds need re-tuning.

Caveats by construction: eager per-step execution measures kernels
*without* XLA's cross-step fusion, so absolute walls sit above the jitted
path — the *ratios between classes* are the calibrated signal, not the
totals.  First-call compile time is excluded via warmup.
"""

from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class CalibrationRow:
    """One executed step of the plan."""

    node: int  # tree node id of the step output
    backend: str  # pallas | dot | einsum
    measured_s: float  # min-over-repeat eager wall, block_until_ready
    modeled_s: float  # refiner / cost-model per-slice seconds
    flops: float  # modeled real-multiply FLOPs of the step (per slice)
    precision: str = "fp32"  # operand precision

    @property
    def cls(self) -> str:
        """Calibration class: the backend, qualified by precision when
        the step does not run at full fp32 (``pallas[bf16]``, …) — bf16
        steps hit a different roofline, so folding them into the fp32
        rows would skew both ratios."""
        if self.precision == "fp32":
            return self.backend
        return f"{self.backend}[{self.precision}]"

    @property
    def ratio(self) -> float:
        return self.measured_s / self.modeled_s if self.modeled_s else float("inf")


@dataclasses.dataclass
class CalibrationReport:
    rows: list[CalibrationRow]
    backend: str  # the plan's execution backend ("einsum" | "gemm")
    num_steps: int
    peak_bytes: int  # certified naive live-set peak (lowering/memory.py)
    peak_bytes_hoisted: int  # certified prologue/epilogue peak

    def ratio_by_class(self) -> dict[str, dict]:
        """Per backend class: total measured, total modeled, their ratio,
        and the step count — the headline calibration table."""
        agg: dict[str, dict] = {}
        for r in self.rows:
            a = agg.setdefault(
                r.cls,
                {"count": 0, "measured_s": 0.0, "modeled_s": 0.0},
            )
            a["count"] += 1
            a["measured_s"] += r.measured_s
            a["modeled_s"] += r.modeled_s
        for a in agg.values():
            a["ratio"] = (
                a["measured_s"] / a["modeled_s"]
                if a["modeled_s"]
                else float("inf")
            )
        return agg

    def table(self) -> str:
        """Markdown model-vs-measured table per backend class."""
        lines = [
            "| class | steps | measured (s) | modeled (s) | meas/model |",
            "|---|---|---|---|---|",
        ]
        for cls, a in sorted(self.ratio_by_class().items()):
            lines.append(
                f"| {cls} | {a['count']} | {a['measured_s']:.3e} "
                f"| {a['modeled_s']:.3e} | {a['ratio']:.2f} |"
            )
        return "\n".join(lines)

    def summary(self) -> dict:
        """JSON-serializable form (trajectory records, CI artifacts)."""
        return {
            "backend": self.backend,
            "num_steps": self.num_steps,
            "peak_bytes": self.peak_bytes,
            "peak_bytes_hoisted": self.peak_bytes_hoisted,
            "by_class": self.ratio_by_class(),
        }


def _time_call(fn, repeat: int) -> tuple[float, object]:
    """Min-over-repeat eager wall of ``fn()`` with a device fence; one
    untimed warmup call first so backend compilation (Pallas kernels
    compile on first dispatch) never pollutes the measurement."""
    import jax

    out = jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(1, repeat)):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best, out


def calibrate_plan(plan, arrays, slice_id: int = 0, repeat: int = 2):
    """Execute one slice of ``plan`` step-by-step (eagerly, fenced) and
    join each step's measured wall with its modeled per-slice time.

    Each step runs the executor's own lane-dense dispatch
    (:func:`repro.lowering.gemm_form.contract_flat`) on flat buffers.
    Returns a :class:`CalibrationReport`.
    """
    import jax.numpy as jnp
    from jax import lax

    from ..core.merging import modeled_node_time
    from ..lowering import gemm_form
    from ..obs import trace

    # slice the leaves for the concrete slice assignment
    svals = [(slice_id >> p) & 1 for p in range(plan.num_sliced)]
    env: dict[int, object] = {}
    for i in range(len(arrays)):
        a = jnp.asarray(arrays[i])
        for axis, spos in plan.leaf_specs[i]:
            a = lax.index_in_dim(a, svals[spos], axis=axis, keepdims=False)
        env[i] = a.reshape(-1)

    n_sub = 1 << plan.num_sliced
    rows: list[CalibrationRow] = []
    for k, st in enumerate(plan.steps):
        a, b = env[st.lhs], env[st.rhs]
        spec = plan.schedule.specs[k] if plan.schedule is not None else None
        ds = plan.dense_steps[k]
        with trace.span("calib.node", cat="calib", node=st.out):
            measured, out = _time_call(
                lambda: gemm_form.contract_flat(spec, ds, a, b), repeat
            )
        if spec is None:
            modeled = (
                modeled_node_time(plan.tree, st.out, plan.smask) / n_sub
            )
            cls, flops, prec = "einsum", 0.0, "fp32"
        else:
            modeled = spec.modeled_time_s
            cls = spec.backend
            flops = spec.form.flops
            prec = getattr(spec, "precision", "fp32")
        env[st.out] = out
        rows.append(
            CalibrationRow(
                node=st.out,
                backend=cls,
                measured_s=measured,
                modeled_s=modeled,
                flops=flops,
                precision=prec,
            )
        )

    mem = plan.memory_plan()
    return CalibrationReport(
        rows=rows,
        backend=plan.backend,
        num_steps=len(plan.steps),
        peak_bytes=mem.peak_bytes,
        peak_bytes_hoisted=mem.peak_bytes_hoisted,
    )
