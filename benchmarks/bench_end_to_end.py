"""Sec. VI-B — end-to-end contraction: paper-faithful pipeline vs greedy
baseline, measured on the real executor (CPU), plus the projected
single-chip TPU time from the F-surface model for the planner's output.

The paper's headline (304 s → 149.2 s on 107,520 Sunway nodes) is a
planner+efficiency product; at our scale we report the same decomposition:
  time = C(B)·O(B,S) / (peak · efficiency)
"""

from __future__ import annotations

import numpy as np

from repro.core import plan_contraction
from repro.core.executor import ContractionPlan
from repro.core.merging import modeled_tree_time

from .common import append_trajectory, network_for, timer


def run(circuit: str = "syc-12") -> list[str]:
    tn, arrays = network_for(circuit)
    rows = []
    results = {}
    # slice to width-3: a few slices, the stem-dominant regime the paper
    # targets (deep slicing of small circuits is planner-hostile for every
    # method and CPU-hostile for the executor)
    plans = {}
    for label, kw in (
        ("greedy_base", dict(method="greedy", tune=False, merge=False)),
        ("paper_faithful", dict(method="lifetime", tune=True, merge=True)),
    ):
        tree, smask, report = plan_contraction(
            tn, max(tree_width(tn) - 3, 10), seed=0, **kw
        )
        plans[label] = (tree, smask, report)
    for label, (tree, smask, report) in plans.items():
        plan = ContractionPlan(tree, smask)
        val, t = timer(
            lambda: np.asarray(plan.contract_all(arrays, slice_batch=4)),
            repeat=2,
        )
        results[label] = complex(val)
        # memory columns: planned live-set peak (lifetime buffer plan)
        mem = plan.memory_plan()
        rows.append(
            f"e2e_{label}_ms,{t*1e3:.1f},"
            f"overhead={report.slicing_overhead:.3f};"
            f"slices={report.num_sliced};"
            f"tpu_model_s={modeled_tree_time(tree, smask):.3e};"
            f"peak_bytes={mem.peak_bytes};"
            f"peak_bytes_hoisted={mem.peak_bytes_hoisted}"
        )
    assert abs(results["greedy_base"] - results["paper_faithful"]) < 1e-4, (
        "pipelines disagree on the amplitude!"
    )
    rows.extend(telemetry_rows())
    return rows


def precision_rows(
    circuit: str = "syc-12",
    target_dim: int = 18,
    fidelity_tol: float = 0.05,
    trajectory_dir: str = "experiments/precision",
) -> list[str]:
    """Mixed-precision ablation on the pinned plan: the same network
    planned at fp32 and under REPRO_PRECISION=auto semantics
    (``precision="auto"`` at the given XEB budget), comparing modeled
    two-phase time, modeled HBM traffic, slice count, bf16 step counts,
    the measured contract_all wall, and the measured Linear-XEB delta on
    the open-batch amplitudes — appended to the trajectory history
    ``make_tables`` renders."""
    from repro.core import plan_compiled, sample_bitstrings
    from repro.quantum.xeb import xeb_from_amplitudes

    from .common import CIRCUITS

    tn, arrays = network_for(circuit)
    circ = CIRCUITS[circuit]()
    stats, xebs = {}, {}
    for label, prec in (("fp32", "fp32"), ("auto", "auto")):
        plan, report = plan_compiled(
            tn, target_dim, backend="gemm", use_cache=False,
            slicing_mode="peak", precision=prec,
            fidelity_tol=fidelity_tol,
        )
        val, wall = timer(
            lambda: np.asarray(plan.contract_all(arrays, slice_batch=8)),
            repeat=2,
        )
        n_slices = 1 << plan.num_sliced
        epi = sum(
            plan.schedule.specs[k].modeled_time_s
            for k in plan.epilogue_idx
        ) * n_slices
        stats[label] = {
            "amp": complex(val),
            "wall_s": wall,
            "num_sliced": plan.num_sliced,
            "modeled_time_s": report.modeled_time_hoisted_s,
            "modeled_epilogue_s": epi,
            "hbm_bytes": plan.schedule.hbm_traffic_bytes() * n_slices,
            "peak_bytes": report.peak_bytes,
            "precision_counts": plan.schedule.precision_counts(),
            "predicted_amp_error": report.predicted_amp_error,
        }
        res = sample_bitstrings(
            circ, num_samples=128,
            open_qubits=tuple(range(circ.num_qubits - 4,
                                    circ.num_qubits)),
            target_dim=target_dim, seed=1, backend="gemm",
            use_cache=False, slicing_mode="peak", slice_batch=4,
            precision=prec, fidelity_tol=fidelity_tol,
        )
        xebs[label] = xeb_from_amplitudes(
            circ.num_qubits, np.asarray(res.batch.amplitudes).ravel()
        )
    f32, aut = stats["fp32"], stats["auto"]
    rel_err = abs(aut["amp"] - f32["amp"]) / abs(f32["amp"])
    assert rel_err <= fidelity_tol, (
        f"auto amplitude drifted {rel_err:.3g} > tol {fidelity_tol}"
    )
    record = {
        "workload": circuit,
        "fidelity_tol": fidelity_tol,
        "fp32": {k: v for k, v in f32.items() if k != "amp"},
        "auto": {k: v for k, v in aut.items() if k != "amp"},
        "amp_rel_err": rel_err,
        "xeb_fp32": xebs["fp32"],
        "xeb_auto": xebs["auto"],
        "xeb_delta": xebs["auto"] - xebs["fp32"],
        "modeled_epilogue_speedup": (
            f32["modeled_epilogue_s"] / aut["modeled_epilogue_s"]
            if aut["modeled_epilogue_s"] else None
        ),
    }
    append_trajectory([record], trajectory_dir)
    rows = []
    for label in ("fp32", "auto"):
        s = stats[label]
        counts = ";".join(
            f"{k}:{v}" for k, v in sorted(s["precision_counts"].items())
        )
        rows.append(
            f"e2e_precision_{label}_ms,{s['wall_s']*1e3:.1f},"
            f"slices={s['num_sliced']};"
            f"model_s={s['modeled_time_s']:.3e};"
            f"epilogue_s={s['modeled_epilogue_s']:.3e};"
            f"hbm_bytes={s['hbm_bytes']:.3e};"
            f"counts={counts};"
            f"xeb={xebs[label]:.4f}"
        )
    rows.append(
        f"e2e_precision_delta,{rel_err:.3e},"
        f"xeb_delta={record['xeb_delta']:.4f};"
        f"epilogue_speedup={record['modeled_epilogue_speedup']:.2f};"
        f"tol={fidelity_tol}"
    )
    return rows


def telemetry_rows(
    circuits=("syc-12", "zn-12"),
    trajectory_dir: str = "experiments/obs",
) -> list[str]:
    """Observability ablation on the paper workloads: tracer overhead
    (the same compiled artifact executed untraced and traced,
    min-over-repeat) and the model-vs-measured calibration ratio per
    backend class on the lowered GEMM schedule — appended to the
    trajectory history ``make_tables`` renders.

    Plans are sliced to width ≤ 19 so per-slice tensors stay CPU-sized
    on every workload (zn-12 is width-30 — a full-width contraction is
    hours on CPU).  Small slice counts (≤ 128) measure the full vmapped
    scan; larger ones measure a 16-slice subset of the per-slice
    resumable path via a pre-completed checkpoint — the path where the
    tracer wraps every slice range, i.e. the worst case for overhead."""
    import repro.obs as obs
    from repro.core.distributed import (
        SliceRangeCheckpoint,
        contract_resumable,
    )
    from repro.obs import trace

    import jax

    rows, records = [], []
    prev = trace.enabled()
    try:
        for circuit in circuits:
            tn, arrays = network_for(circuit)
            tree, smask, report = plan_contraction(
                tn, max(min(tree_width(tn) - 3, 19), 10), seed=0,
                method="lifetime", tune=True, merge=True,
            )
            plan = ContractionPlan(tree, smask)
            n_slices = 1 << report.num_sliced
            if n_slices <= 128:
                path = "scan"
                run_once = lambda: np.asarray(
                    plan.contract_all(arrays, slice_batch=4)
                )
            else:
                path = "resumable[0:16)"
                out_shape = jax.eval_shape(
                    lambda: plan.contract_slice(
                        list(arrays), plan.slice_bits(0)
                    )
                )

                def run_once():
                    state = SliceRangeCheckpoint(
                        n_slices,
                        set(range(16, n_slices)),
                        np.zeros(out_shape.shape, out_shape.dtype),
                    )
                    val, _ = contract_resumable(
                        plan, arrays, chunk=4, state=state
                    )
                    return np.asarray(val)

            warm = run_once()  # compile outside both arms
            trace.set_enabled(False)
            val_off, wall_off = timer(run_once, repeat=2)
            trace.set_enabled(True)
            obs.reset()
            val_on, wall_on = timer(run_once, repeat=2)
            assert val_off.tobytes() == val_on.tobytes() == warm.tobytes()
            # calibration on the lowered GEMM schedule so the table
            # covers the refiner's backend classes, not just einsum
            gemm_plan = ContractionPlan(tree, smask, backend="gemm")
            cal = obs.calibrate_plan(gemm_plan, arrays, repeat=1)
            ratio = wall_on / wall_off if wall_off else None
            records.append({
                "workload": circuit,
                "num_sliced": report.num_sliced,
                "path": path,
                "wall_untraced_s": wall_off,
                "wall_traced_s": wall_on,
                "overhead_ratio": ratio,
                "calibration": cal.summary(),
            })
            rows.append(
                f"obs_overhead_{circuit}_ms,{wall_on*1e3:.1f},"
                f"untraced_ms={wall_off*1e3:.1f};ratio={ratio:.3f};"
                f"path={path}"
            )
            for cls, agg in sorted(cal.ratio_by_class().items()):
                rows.append(
                    f"obs_calibration_{circuit}_{cls},"
                    f"{agg['measured_s']*1e6:.1f},"
                    f"steps={agg['count']};"
                    f"modeled_s={agg['modeled_s']:.3e};"
                    f"meas_model={agg['ratio']:.2f}"
                )
    finally:
        trace.set_enabled(prev)
        obs.reset()
    append_trajectory(records, trajectory_dir)
    return rows


def tree_width(tn) -> int:
    from repro.core.pathfinder import random_greedy_tree

    return random_greedy_tree(tn, repeats=4, seed=0).width()


def main() -> None:
    for r in run():
        print(r)


if __name__ == "__main__":
    main()
