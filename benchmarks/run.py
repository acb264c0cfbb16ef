"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.
"""

from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_distributed_scaling,
        bench_end_to_end,
        bench_flops_efficiency,
        bench_roofline,
        bench_sampling_throughput,
        bench_serving,
        bench_slice_count,
        bench_slicefinder_speed,
        bench_slicing_overhead,
    )

    import types

    precision = types.SimpleNamespace(run=bench_end_to_end.precision_rows)
    modules = [
        ("fig8", bench_slicefinder_speed),
        ("fig9", bench_slice_count),
        ("fig10", bench_slicing_overhead),
        ("fig11", bench_flops_efficiency),
        ("e2e", bench_end_to_end),
        ("precision", precision),
        ("sampling", bench_sampling_throughput),
        ("roofline", bench_roofline),
        ("distributed", bench_distributed_scaling),
        ("serving", bench_serving),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.perf_counter()
        try:
            for row in mod.run():
                print(row, flush=True)
        except Exception as e:  # keep the harness alive per-figure
            failures += 1
            print(f"{name}_FAILED,NaN,{e!r}", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(
            f"{name}_wall_s,{(time.perf_counter()-t0)*1e6:.0f},seconds="
            f"{time.perf_counter()-t0:.1f}",
            flush=True,
        )
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
