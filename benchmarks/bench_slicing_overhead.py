"""Fig. 10 + Sec. VI-A — slicing overhead O(B,S) (Eq. 4), modeled AND
measured.

Reports geometric/harmonic mean overhead per circuit for:
  greedy baseline → sliceFinder (Alg. 1) → + tree tuning (Alg. 2).
Paper headline: overhead 1.255 on the contraction path used for Sycamore
(vs Cotengra 431 single-shot / Alibaba 4).

The hoisting section turns Eq. 4 from a planner metric into a runtime
measurement: for each circuit it reports the naive executed-FLOPs
overhead (== Eq. 4) next to the two-phase hoisted one (prologue once +
epilogue per slice, see :mod:`repro.lowering.partition`), and — on the
CPU-tractable instance — *wall-clock* naive vs hoisted execution per
backend.  Records are appended to ``experiments/hoisting/trajectory.
json`` and rendered by ``benchmarks.make_tables``.

The memory section (:func:`memory_rows`) does the same for the
lifetime-based buffer planner: width-proxy vs peak-aware slicing set
sizes, planned live-set peaks, the fused-kernel transpose-bytes credit,
and measured wall-clock of the peak-mode mask on the tractable instance
(records under ``experiments/memory/trajectory.json``)."""

from __future__ import annotations

import math

import numpy as np

from repro.core.executor import ContractionPlan
from repro.core.slicing import find_slices, peak_budget_for_width
from repro.core.tensor_network import popcount
from repro.core.tuning import tuning_slice_finder
from repro.lowering.memory import plan_memory
from repro.lowering.partition import partition_tree
from repro.lowering.refiner import refine_tree_schedule

from .common import append_trajectory, network_for, timer, trees_for


def _geo(xs):
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def _har(xs):
    return len(xs) / sum(1.0 / x for x in xs)


def run(circuits=("syc-12", "syc-16", "syc-20", "zn-16"),
        n_trees: int = 6) -> list[str]:
    rows = []
    for name in circuits:
        tn, _ = network_for(name)
        trees = trees_for(tn, n_trees)
        ov = {"greedy": [], "lifetime": [], "tuned": []}
        for i, tree in enumerate(trees):
            target = max(tree.width() - 4, 8)
            sg = find_slices(tree, target, method="greedy", repeats=4, seed=i)
            ov["greedy"].append(tree.slicing_overhead(sg))
            sl = find_slices(tree, target, method="lifetime")
            ov["lifetime"].append(tree.slicing_overhead(sl))
            res = tuning_slice_finder(tree, target, max_rounds=8)
            ov["tuned"].append(res.tree.slicing_overhead(res.smask))
        rows.append(
            f"fig10_{name}_geomean,{_geo(ov['lifetime']):.3f},"
            f"greedy={_geo(ov['greedy']):.3f};tuned={_geo(ov['tuned']):.3f}"
        )
        rows.append(
            f"fig10_{name}_harmean,{_har(ov['lifetime']):.3f},"
            f"greedy={_har(ov['greedy']):.3f};tuned={_har(ov['tuned']):.3f}"
        )
    # best single overhead on the biggest circuit (paper: 1.255)
    tn, _ = network_for("syc-20")
    best = float("inf")
    for t in trees_for(tn, 4):
        res = tuning_slice_finder(t, max(t.width() - 4, 8), max_rounds=10)
        best = min(best, res.tree.slicing_overhead(res.smask))
    rows.append(f"fig10_best_overhead_syc20,{best:.3f},paper=1.255")
    rows.extend(hoisting_rows())
    rows.extend(memory_rows())
    return rows


def hoisting_rows(
    modeled_circuits=("syc-16", "syc-20"),
    measured_circuit: str = "syc-12",
    backends=("einsum", "gemm"),
    trajectory_dir: str = "experiments/hoisting",
) -> list[str]:
    """Naive vs two-phase hoisted execution: executed-FLOPs overhead on
    the paper circuits (|S| >= 4), wall-clock on the CPU-tractable one.

    Wall-clock is reported twice per backend: *cold* re-materializes the
    slice-invariant prologue (first request of a circuit family) and
    *warm* serves it from the plan's hoist cache (steady-state serving).

    Two execution paths are measured.  On the vmapped-scan path the
    speedup is expectedly ~1.0x on XLA: slice-invariant ops are
    unbatched under ``vmap`` and hoisted out of the scan by the
    compiler's loop-invariant code motion, so two-phase execution makes
    that reclamation *guaranteed by construction* (and portable to paths
    the compiler cannot see across) rather than faster here.  The
    per-slice driver (``contract_resumable`` — independent jit calls,
    the paper's explicit subtask loop, no cross-call LICM possible) is
    where the same split buys measurable wall-clock.
    """
    rows: list[str] = []
    records: list[dict] = []
    # -------- executed-FLOPs overhead, paper instances (no execution).
    # Two memory targets per circuit: at W-4 tuning leaves little
    # invariant waste; at W-8 (deeper slicing, the paper's regime) the
    # hoisted path reclaims a measurable FLOP fraction.
    for name in modeled_circuits:
        tn, _ = network_for(name)
        tree = trees_for(tn, 1)[0]
        for shrink in (4, 8):
            res = tuning_slice_finder(
                tree, max(tree.width() - shrink, 8), max_rounds=8
            )
            n_sliced = popcount(res.smask)
            part = partition_tree(res.tree, res.smask)
            naive = res.tree.slicing_overhead(res.smask)
            hoisted = part.hoisted_overhead()
            rows.append(
                f"hoist_{name}_w{shrink}_overhead,{hoisted:.3f},"
                f"naive_eq4={naive:.3f};"
                f"inv_frac={part.invariant_fraction:.2e};"
                f"slices={n_sliced}"
            )
            records.append({
                "workload": f"{name} (W-{shrink})",
                "kind": "modeled",
                "num_sliced": n_sliced,
                "invariant_fraction": part.invariant_fraction,
                "invariant_nodes": len(part.invariant_nodes),
                "total_nodes": len(part.invariant_nodes)
                + len(part.epilogue_nodes),
                "naive_overhead": naive,
                "hoisted_overhead": hoisted,
            })
    # -------- measured wall-clock, tractable instance, both backends
    tn, arrays = network_for(measured_circuit)
    tree = trees_for(tn, 1)[0]
    res = tuning_slice_finder(tree, max(tree.width() - 4, 8), max_rounds=8)
    n_sliced = popcount(res.smask)
    part = partition_tree(res.tree, res.smask)
    for backend in backends:
        plan = ContractionPlan(res.tree, res.smask, backend=backend)
        ref, t_naive = timer(
            lambda: np.asarray(
                plan.contract_all(arrays, slice_batch=4, hoist=False)
            ),
            repeat=2,
        )

        def hoisted_cold():
            plan._hoist_cache.clear()  # force prologue re-materialization
            return np.asarray(
                plan.contract_all(arrays, slice_batch=4, hoist=True)
            )

        got, t_cold = timer(hoisted_cold, repeat=2)
        assert np.allclose(got, ref, atol=1e-5)  # sanity: modes agree
        _, t_warm = timer(
            lambda: np.asarray(
                plan.contract_all(arrays, slice_batch=4, hoist=True)
            ),
            repeat=2,
        )
        # the per-slice driver: one jit call per subtask, so invariant
        # recomputation is real unless explicitly hoisted
        from repro.core.distributed import contract_resumable

        _, t_ps_naive = timer(
            lambda: contract_resumable(
                plan, arrays, chunk=16, hoist=False
            )[0],
            repeat=2,
        )
        got_ps, t_ps_hoist = timer(
            lambda: contract_resumable(plan, arrays, chunk=16, hoist=True)[0],
            repeat=2,
        )
        assert np.allclose(got_ps, ref, atol=1e-5)
        rows.append(
            f"hoist_measured_{measured_circuit}_{backend}_ms,"
            f"{t_cold*1e3:.1f},naive={t_naive*1e3:.1f}ms;"
            f"warm={t_warm*1e3:.1f}ms;"
            f"perslice={t_ps_hoist*1e3:.1f}ms;"
            f"perslice_naive={t_ps_naive*1e3:.1f}ms;"
            f"perslice_speedup={t_ps_naive/t_ps_hoist:.2f}x"
        )
        records.append({
            "workload": measured_circuit,
            "kind": "measured",
            "backend": backend,
            "wall_perslice_naive_s": t_ps_naive,
            "wall_perslice_hoisted_s": t_ps_hoist,
            "speedup_perslice": t_ps_naive / t_ps_hoist,
            "num_sliced": n_sliced,
            "invariant_fraction": part.invariant_fraction,
            "invariant_nodes": len(part.invariant_nodes),
            "total_nodes": len(part.invariant_nodes)
            + len(part.epilogue_nodes),
            "naive_overhead": res.tree.slicing_overhead(res.smask),
            "hoisted_overhead": part.hoisted_overhead(),
            "wall_naive_s": t_naive,
            "wall_hoisted_cold_s": t_cold,
            "wall_hoisted_warm_s": t_warm,
            "speedup_cold": t_naive / t_cold,
            "speedup_warm": t_naive / t_warm,
        })
    append_trajectory(records, trajectory_dir)
    return rows


def memory_rows(
    modeled_circuits=("syc-16", "syc-20"),
    measured_circuit: str = "syc-12",
    n_trees: int = 3,
    trajectory_dir: str = "experiments/memory",
) -> list[str]:
    """Lifetime-based memory planning: width-proxy vs peak-aware slicing
    (|S|, planned live-set peaks) and the fused-kernel transpose-bytes
    credit, modeled on the paper instances; wall-clock on the
    CPU-tractable one.

    The peak-aware slicer's |S| reduction multiplies straight into
    ``contract_all`` wall-clock (half the sliced indices = a quarter of
    the subtasks), so the measured section times the PR-3 hoisted
    baseline (width-mode slicing) against the same executor running the
    peak-mode mask.  Peaks are planned (exact live-set algebra,
    property-tested against brute force); on the measured instance the
    *residency delta* of the peak-mode run — live device bytes added by
    it, sampled via ``jax.live_arrays`` before/after — is recorded as a
    steady-state footprint observation (CPU jax exposes no in-flight
    peak counter; fused kernels execute via the interpret-mode emulator
    on CPU, so their bandwidth win is likewise reported as modeled
    bytes, not wall-clock).
    """
    import jax

    rows: list[str] = []
    records: list[dict] = []
    for name in modeled_circuits + (measured_circuit,):
        measured = name == measured_circuit
        tn, arrays = network_for(name)
        for i, tree in enumerate(trees_for(tn, n_trees)):
            target = max(tree.width() - 4, 8)
            S_w = find_slices(tree, target, method="lifetime")
            S_p = find_slices(tree, target, method="lifetime", mode="peak")
            mem_w = plan_memory(tree, S_w)
            mem_p = plan_memory(tree, S_p)
            # fused-kernel transpose credit for the peak-mode schedule
            # (planner-side refinement — syc-16/20 are planning-only)
            sched = refine_tree_schedule(tree, S_p)
            rec = {
                "workload": f"{name} t{i}",
                "kind": "modeled",
                "target_dim": target,
                "budget_bytes": max(
                    peak_budget_for_width(target), mem_w.peak_bytes
                ),
                "num_sliced_width": popcount(S_w),
                "num_sliced_peak": popcount(S_p),
                "peak_bytes_width": mem_w.peak_bytes,
                "peak_bytes_peak": mem_p.peak_bytes,
                "peak_bytes_hoisted_peak": mem_p.peak_bytes_hoisted,
                "buffer_slots": mem_p.buffer_slots,
                "transpose_bytes_paid": sched.transpose_bytes(),
            }
            if measured and i == 0:
                plan_w = ContractionPlan(tree, S_w)
                plan_p = ContractionPlan(tree, S_p)
                ref, t_w = timer(
                    lambda: np.asarray(
                        plan_w.contract_all(arrays, slice_batch=4, hoist=True)
                    ),
                    repeat=2,
                )
                # residency attributable to the peak-mode run: live device
                # bytes added by it (result, hoisted-frontier cache,
                # compiled constants).  CPU jax exposes no in-flight
                # peak counter, so this is steady-state residency — the
                # in-flight bound is the *planned* peak above, which is
                # exact by construction (property-tested).
                live_before = sum(a.nbytes for a in jax.live_arrays())
                got, t_p = timer(
                    lambda: np.asarray(
                        plan_p.contract_all(arrays, slice_batch=4, hoist=True)
                    ),
                    repeat=2,
                )
                live_delta = (
                    sum(a.nbytes for a in jax.live_arrays()) - live_before
                )
                assert np.allclose(got, ref, atol=1e-5)  # masks agree
                rec.update({
                    "kind": "measured",
                    "wall_width_s": t_w,
                    "wall_peak_s": t_p,
                    "speedup_peak_over_width": t_w / t_p,
                    "measured_resident_delta_bytes": int(live_delta),
                })
                rows.append(
                    f"memory_measured_{name}_ms,{t_p*1e3:.1f},"
                    f"width={t_w*1e3:.1f}ms;"
                    f"speedup={t_w/t_p:.2f}x;"
                    f"slices={popcount(S_w)}->{popcount(S_p)};"
                    f"resident_delta_bytes={int(live_delta)}"
                )
            records.append(rec)
            rows.append(
                f"memory_{name}_t{i}_peak_bytes,{mem_p.peak_bytes},"
                f"width_peak={mem_w.peak_bytes};"
                f"S={popcount(S_w)}->{popcount(S_p)};"
                f"tb_paid={sched.transpose_bytes():.3e}"
            )
    append_trajectory(records, trajectory_dir)
    return rows


def main() -> None:
    for r in run():
        print(r)


if __name__ == "__main__":
    main()
