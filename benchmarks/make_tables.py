"""Generate the EXPERIMENTS.md §Dry-run and §Roofline markdown tables from
experiments/dryrun/*.json, plus the §Sampling throughput table when
``benchmarks.bench_sampling_throughput --json`` output is present under
experiments/sampling/, the §Lowering backend table from the trajectory
records ``benchmarks.bench_flops_efficiency`` appends under
experiments/lowering/, the §Hoisting table (naive vs two-phase
sliced execution) from the records ``benchmarks.bench_slicing_overhead``
appends under experiments/hoisting/, the §Memory table (peak-aware
slicer vs width proxy + fused transpose credit) from the records the
same benchmark's ``memory_rows`` appends under experiments/memory/, the §Co-optimizer table (one-shot
pipeline vs anytime plan_search) from the records
``benchmarks.bench_slice_count.cooptimizer_rows`` appends under
experiments/optimize/, and the §Observability table (tracer
overhead + model-vs-measured calibration) from the records
``bench_end_to_end.telemetry_rows`` appends under experiments/obs/.

    PYTHONPATH=src python -m benchmarks.make_tables > experiments/tables.md
"""

from __future__ import annotations

import glob
import json
import os

from repro.configs import SHAPES, get_config

from .bench_roofline import enrich

SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
ARCH_ORDER = [
    "llama3-405b", "llama3.2-3b", "qwen3-4b", "deepseek-7b", "zamba2-7b",
    "seamless-m4t-medium", "deepseek-moe-16b", "llama4-scout-17b-a16e",
    "qwen2-vl-72b", "mamba2-130m",
]


def fmt_bytes(b):
    if b is None:
        return "-"
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if b < 1024:
            return f"{b:.1f}{unit}"
        b /= 1024
    return f"{b:.1f}PB"


def fmt_s(x):
    if x is None:
        return "-"
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.2f}ms"
    return f"{x*1e6:.1f}µs"


def load(dryrun_dir="experiments/dryrun"):
    """One record per cell: the run whose sharding recipe matches the
    arch's production recipe (experiment variants like __rfsdp_only are
    §Perf baselines, not table rows)."""
    recs = {}
    for path in glob.glob(os.path.join(dryrun_dir, "*.json")):
        with open(path) as f:
            r = json.load(f)
        arch = r.get("arch")
        try:
            want = get_config(arch).sharding_recipe
        except KeyError:
            continue
        got = r.get("recipe")
        if got is not None and got != want:
            continue
        key = (arch, r.get("shape"), "multi" in os.path.basename(path))
        recs[key] = r
    return recs


def print_sampling_table(sampling_dir="experiments/sampling") -> None:
    """§Sampling throughput rows (batched correlated-amplitude sampling),
    emitted only when the benchmark's JSON records exist."""
    paths = sorted(glob.glob(os.path.join(sampling_dir, "*.json")))
    if not paths:
        return
    print("\n### Batch-sampling throughput "
          "(one sliced contraction per 2^k batch)\n")
    print("| k open | batch | slices | wall | samples/s | "
          "batch amps/s | per-amp engine amps/s | XEB |")
    print("|---|---|---|---|---|---|---|---|")
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        for r in rec.get("records", []):
            print(
                f"| {r['k_open']} | {r['batch_size']} | {r['num_slices']} "
                f"| {fmt_s(r['wall_s'])} | {r['samples_per_s']:.0f} "
                f"| {r['amps_per_s']:.1f} "
                f"| {r['per_amp_engine_amps_per_s']:.1f} "
                f"| {r['xeb']:+.3f} |"
            )


def print_lowering_table(lowering_dir="experiments/lowering") -> None:
    """§Lowering backend rows (einsum oracle vs lowered-GEMM schedule),
    one row per trajectory record."""
    paths = sorted(glob.glob(os.path.join(lowering_dir, "*.json")))
    rows = []
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        rows.extend(rec.get("records", []))
    if not rows:
        return
    print("\n### Lowered-GEMM backend vs einsum oracle (stem workload)\n")
    print("| workload | einsum wall | gemm wall | gemm/einsum | "
          "schedule (nodes per backend) | pad waste |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        be = r.get("backends", {})
        sched = be.get("gemm", {}).get("schedule", {})
        counts = ", ".join(
            f"{k}:{v}" for k, v in sorted(sched.get("backends", {}).items())
        ) or "-"
        print(
            f"| {r.get('workload', '-')} "
            f"| {fmt_s(be.get('einsum', {}).get('wall_s'))} "
            f"| {fmt_s(be.get('gemm', {}).get('wall_s'))} "
            f"| {r.get('gemm_over_einsum', float('nan')):.2f}× "
            f"| {counts} "
            f"| {sched.get('pad_waste', 0.0)*100:.1f}% |"
        )


def print_hoisting_table(hoisting_dir="experiments/hoisting") -> None:
    """§Hoisting rows: naive (full tree per slice, Eq. 4) vs two-phase
    lifetime-partitioned execution, one row per trajectory record."""
    paths = sorted(glob.glob(os.path.join(hoisting_dir, "*.json")))
    rows = []
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        rows.extend(rec.get("records", []))
    if not rows:
        return
    print("\n### Two-phase sliced execution "
          "(slice-invariant hoisting vs naive, Eq. 4)\n")
    print("| workload | backend | slices | inv. nodes | naive ov (Eq. 4) | "
          "hoisted ov | scan wall (naive / hoisted warm) | "
          "per-slice wall (naive / hoisted) | per-slice speedup |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        inv = (
            f"{r['invariant_nodes']}/{r['total_nodes']}"
            if "invariant_nodes" in r else "-"
        )
        wall_scan = (
            f"{fmt_s(r['wall_naive_s'])} / {fmt_s(r['wall_hoisted_warm_s'])}"
            if r.get("wall_naive_s") is not None else "-"
        )
        wall_ps = (
            f"{fmt_s(r['wall_perslice_naive_s'])} / "
            f"{fmt_s(r['wall_perslice_hoisted_s'])}"
            if r.get("wall_perslice_naive_s") is not None else "-"
        )
        speed = r.get("speedup_perslice")
        print(
            f"| {r.get('workload', '-')} "
            f"| {r.get('backend', 'modeled')} "
            f"| {1 << r.get('num_sliced', 0)} "
            f"| {inv} "
            f"| {r.get('naive_overhead', float('nan')):.3f} "
            f"| {r.get('hoisted_overhead', float('nan')):.3f} "
            f"| {wall_scan} | {wall_ps} "
            f"| {'-' if speed is None else f'{speed:.2f}×'} |"
        )


def print_memory_table(memory_dir="experiments/memory") -> None:
    """§Memory rows: width-proxy vs peak-aware slicing (lifetime-based
    buffer plans) + fused-kernel transpose credit, one row per
    trajectory record."""
    paths = sorted(glob.glob(os.path.join(memory_dir, "*.json")))
    rows = []
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        if isinstance(rec, dict):
            rows.extend(rec.get("records", []))
    if not rows:
        return
    print("\n### Lifetime-based memory planning "
          "(peak-aware slicer vs width proxy, fused transpose credit)\n")
    print("| workload | \\|S\\| width → peak | planned peak width → peak | "
          "byte budget | transpose bytes paid | "
          "wall width → peak | speedup |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        if "num_sliced_width" not in r:
            continue
        wall = speed = "-"
        if r.get("wall_width_s") is not None:
            wall = (
                f"{fmt_s(r['wall_width_s'])} → {fmt_s(r['wall_peak_s'])}"
            )
            speed = f"{r['speedup_peak_over_width']:.2f}×"
        print(
            f"| {r.get('workload', '-')} "
            f"| {r['num_sliced_width']} → {r['num_sliced_peak']} "
            f"| {fmt_bytes(r['peak_bytes_width'])} → "
            f"{fmt_bytes(r['peak_bytes_peak'])} "
            f"| {fmt_bytes(r.get('budget_bytes'))} "
            f"| {fmt_bytes(r.get('transpose_bytes_paid'))} "
            f"| {wall} | {speed} |"
        )


def print_optimize_table(optimize_dir="experiments/optimize") -> None:
    """§Co-optimizer rows: one-shot staged pipeline vs the anytime
    path–slice co-optimizer at equal evaluation budget and equal
    certified-peak byte budget, one row per trajectory record."""
    paths = sorted(glob.glob(os.path.join(optimize_dir, "*.json")))
    rows = []
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        if isinstance(rec, dict):
            rows.extend(rec.get("records", []))
    if not rows:
        return
    print("\n### Anytime path–slice co-optimizer "
          "(one-shot pipeline vs plan_search, equal certified-peak "
          "budget)\n")
    print("| workload | evals | \\|S\\| one-shot → co-opt | "
          "log2 executed FLOPs (hoist-aware) | improvement | "
          "certified peak → budget | plan wall one-shot → search |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        if "log2_flops_oneshot" not in r:
            continue
        print(
            f"| {r.get('workload', '-')} "
            f"| {r.get('max_evals', '-')} "
            f"| {r['num_sliced_oneshot']} → {r['num_sliced_coopt']} "
            f"| {r['log2_flops_oneshot']:.2f} → "
            f"{r['log2_flops_coopt']:.2f} "
            f"| {r['improvement']:.2f}× "
            f"| {fmt_bytes(r['peak_bytes_coopt'])} → "
            f"{fmt_bytes(r['budget_bytes'])} "
            f"| {fmt_s(r.get('wall_oneshot_s'))} → "
            f"{fmt_s(r.get('wall_search_s'))} |"
        )


def print_distributed_table(distributed_dir="experiments/distributed") -> None:
    """§Multi-host rows: static uniform split vs LPT + work stealing
    (measured threaded walls on the synthetic ragged-cost overlay) and
    the real overlapped-reduction execution, one row per trajectory
    record from ``bench_distributed_scaling``."""
    paths = sorted(glob.glob(os.path.join(distributed_dir, "*.json")))
    rows = []
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        if isinstance(rec, dict):
            rows.extend(rec.get("records", []))
    sched = [r for r in rows if r.get("kind") == "scheduling"]
    execs = [r for r in rows if r.get("kind") == "execution"]
    if sched:
        print("\n### Multi-host scheduling "
              "(static uniform split vs LPT + work stealing, "
              "measured walls on ragged costs)\n")
        print("| workload | slices | hosts | imbalance static → steal | "
              "steals | wall static → steal | speedup |")
        print("|---|---|---|---|---|---|---|")
        for r in sched:
            print(
                f"| {r.get('workload', '-')} "
                f"| {r.get('n_slices', '-')} "
                f"| {r.get('hosts', '-')} "
                f"| {r.get('schedule_imbalance_static', 0):.2f} → "
                f"{r.get('schedule_imbalance', 0):.2f} "
                f"| {r.get('steal_count', '-')} "
                f"| {fmt_s(r.get('wall_static_s'))} → "
                f"{fmt_s(r.get('wall_steal_s'))} "
                f"| {r.get('speedup', 0):.2f}× |"
            )
    if execs:
        print("\n### Multi-host execution "
              "(contract_multihost, overlapped chunked all-reduce)\n")
        print("| workload | slices | executed | padded | overlap | "
              "max abs err | wall |")
        print("|---|---|---|---|---|---|---|")
        for r in execs:
            print(
                f"| {r.get('workload', '-')} "
                f"| {r.get('n_slices', '-')} "
                f"| {r.get('executed_slices', '-')} "
                f"| {r.get('padded_slices', '-')} "
                f"| {r.get('overlap_fraction', 0):.2f} "
                f"| {r.get('max_abs_err', 0):.1e} "
                f"| {fmt_s(r.get('wall_s'))} |"
            )


def print_obs_table(obs_dir="experiments/obs") -> None:
    """§Observability rows: tracer-overhead ablation (same compiled
    artifact, untraced vs traced wall) and the model-vs-measured
    calibration ratio per backend class, one row per (workload, class)
    from the trajectory records ``bench_end_to_end.telemetry_rows``
    appends."""
    path = os.path.join(obs_dir, "trajectory.json")
    rows = []
    if os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
        if isinstance(rec, dict):
            rows = rec.get("records", [])
    rows = [r for r in rows if "overhead_ratio" in r]
    if not rows:
        return
    print("\n### Observability "
          "(tracer overhead + model-vs-measured calibration)\n")
    print("| workload | slices | wall untraced → traced | overhead | "
          "class | steps | measured | modeled | meas/model |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        ratio = r.get("overhead_ratio")
        lead = (
            f"| {r.get('workload', '-')} "
            f"| {1 << r.get('num_sliced', 0)} "
            f"| {fmt_s(r.get('wall_untraced_s'))} → "
            f"{fmt_s(r.get('wall_traced_s'))} "
            f"| {'-' if ratio is None else f'{ratio:.3f}×'} "
        )
        by_class = (r.get("calibration") or {}).get("by_class", {})
        if not by_class:
            print(lead + "| - | - | - | - | - |")
            continue
        for i, (cls, agg) in enumerate(sorted(by_class.items())):
            head = lead if i == 0 else "| | | | "
            print(
                head
                + f"| {cls} | {agg['count']} "
                f"| {fmt_s(agg['measured_s'])} "
                f"| {fmt_s(agg['modeled_s'])} "
                f"| {agg['ratio']:.2f} |"
            )


def print_precision_table(precision_dir="experiments/precision") -> None:
    """§Mixed precision rows: fp32 vs auto plan on the pinned workload —
    modeled epilogue time, total HBM traffic, slice count, bf16 step
    counts, and the measured Linear-XEB delta, one row pair per
    trajectory record ``bench_end_to_end.precision_rows`` appends."""
    path = os.path.join(precision_dir, "trajectory.json")
    rows = []
    if os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
        if isinstance(rec, dict):
            rows = rec.get("records", [])
    rows = [r for r in rows if "xeb_delta" in r]
    if not rows:
        return
    print("\n### Mixed precision under an XEB budget "
          "(fp32 vs auto at fidelity_tol)\n")
    print("| workload | tol | mode | slices | bf16 steps | "
          "epilogue model | HBM bytes | wall | XEB | amp rel err |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        for mode in ("fp32", "auto"):
            s = r.get(mode) or {}
            counts = s.get("precision_counts") or {}
            total = sum(counts.values())
            xeb = r.get(f"xeb_{mode}")
            rel_err = (
                "" if mode == "fp32"
                else f"{r.get('amp_rel_err', 0):.2e}"
            )
            print(
                f"| {r.get('workload', '-') if mode == 'fp32' else ''} "
                f"| {r.get('fidelity_tol', '-') if mode == 'fp32' else ''} "
                f"| {mode} "
                f"| {s.get('num_sliced', '-')} "
                f"| {counts.get('bf16', 0)}/{total} "
                f"| {fmt_s(s.get('modeled_epilogue_s'))} "
                f"| {s.get('hbm_bytes', 0):.2e} "
                f"| {fmt_s(s.get('wall_s'))} "
                f"| {'-' if xeb is None else f'{xeb:.4f}'} "
                f"| {rel_err} |"
            )
        print(
            f"| | | Δ | | | "
            f"{r.get('modeled_epilogue_speedup', 0):.2f}× faster | | | "
            f"xeb Δ {r.get('xeb_delta', 0):+.4f} | |"
        )


def print_serving_table(serving_dir="experiments/serving") -> None:
    """§Serving rows from ``benchmarks.bench_serving`` trajectory
    records: cold-vs-warm latency per circuit family, the coalesced
    batched-vs-serial throughput comparison, and the Poisson mixed-
    traffic steady state."""
    path = os.path.join(serving_dir, "trajectory.json")
    rows = []
    if os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
        if isinstance(rec, dict):
            rows = rec.get("records", [])
    cw = [r for r in rows if r.get("kind") == "cold_warm"]
    bt = [r for r in rows if r.get("kind") == "batching"]
    po = [r for r in rows if r.get("kind") == "poisson"]
    if cw:
        print("\n### Serving: cold vs warm "
              "(plan cache across tenant bursts)\n")
        print("| family | tenants | cold p50 / p99 | warm p50 / p99 | "
              "warm p50 speedup | warm req/s |")
        print("|---|---|---|---|---|---|")
        for r in cw:
            print(
                f"| {r.get('family', '-')} | {r.get('tenants', '-')} "
                f"| {fmt_s(r.get('cold_p50_s'))} / "
                f"{fmt_s(r.get('cold_p99_s'))} "
                f"| {fmt_s(r.get('warm_p50_s'))} / "
                f"{fmt_s(r.get('warm_p99_s'))} "
                f"| {r.get('warm_p50_speedup', 0):.1f}× "
                f"| {r.get('warm_req_per_s', 0):.0f} |"
            )
    if bt:
        print("\n### Serving: coalesced batching vs serial "
              "(concurrent amplitude tenants, warm plans)\n")
        print("| family | tenants | batched req/s (p50) | "
              "serial req/s (p50) | gain |")
        print("|---|---|---|---|---|")
        for r in bt:
            print(
                f"| {r.get('family', '-')} | {r.get('tenants', '-')} "
                f"| {r.get('batched_req_per_s', 0):.0f} "
                f"({fmt_s(r.get('batched_p50_s'))}) "
                f"| {r.get('serial_req_per_s', 0):.0f} "
                f"({fmt_s(r.get('serial_p50_s'))}) "
                f"| {r.get('throughput_gain', 0):.2f}× |"
            )
    if po:
        print("\n### Serving: Poisson mixed traffic (steady state)\n")
        print("| families | requests | offered | served req/s | "
              "p50 | p99 | batched |")
        print("|---|---|---|---|---|---|---|")
        for r in po:
            print(
                f"| {r.get('families', '-')} | {r.get('requests', '-')} "
                f"| {r.get('offered_rate_hz', 0):.0f} Hz "
                f"| {r.get('req_per_s', 0):.0f} "
                f"| {fmt_s(r.get('p50_s'))} | {fmt_s(r.get('p99_s'))} "
                f"| {r.get('batched_fraction', 0)*100:.0f}% |"
            )


def main() -> None:
    recs = load()
    # ---------------- dry-run table (both meshes) ----------------
    print("### Dry-run matrix (lower + compile status, per-device memory)\n")
    print("| arch | shape | 16x16 | 2x16x16 | args/dev | temps/dev | "
          "collectives (single-pod) |")
    print("|---|---|---|---|---|---|---|")
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            r1 = recs.get((arch, shape, False))
            r2 = recs.get((arch, shape, True))
            if r1 is None and r2 is None:
                continue
            def status(r):
                if r is None:
                    return "–"
                if "error" in r:
                    return "FAIL"
                if "skipped" in r:
                    return "skip"
                return "OK"
            mem = arg = coll = "-"
            if r1 and "roofline" in r1:
                m = r1["memory"]
                arg = fmt_bytes(m.get("argument_bytes"))
                mem = fmt_bytes(m.get("temp_bytes"))
                cb = r1["roofline"]["collective_bytes_per_device"]
                coll = ", ".join(
                    f"{k}:{fmt_bytes(v)}" for k, v in sorted(cb.items())
                ) or "none"
            print(f"| {arch} | {shape} | {status(r1)} | {status(r2)} "
                  f"| {arg} | {mem} | {coll} |")
    # ---------------- roofline table (single-pod) ----------------
    print("\n### Roofline (single-pod 16x16, per-device terms)\n")
    print("| arch | shape | compute | memory | collective | dominant | "
          "bound | MODEL/HLO | frac |")
    print("|---|---|---|---|---|---|---|---|---|")
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            r = recs.get((arch, shape, False))
            if r is None or "roofline" not in r:
                continue
            e = enrich(r)
            print(
                f"| {arch} | {shape} | {fmt_s(e['compute_s'])} "
                f"| {fmt_s(e['memory_s'])} | {fmt_s(e['collective_s'])} "
                f"| {e['dominant']} | {fmt_s(e['bound_s'])} "
                f"| {e['useful_ratio']:.2f} | {e['roofline_fraction']:.2f} |"
            )
    print_sampling_table()
    print_lowering_table()
    print_hoisting_table()
    print_memory_table()
    print_optimize_table()
    print_obs_table()
    print_precision_table()
    print_distributed_table()
    print_serving_table()


if __name__ == "__main__":
    main()
