#!/usr/bin/env python3
"""Trace one cell's window with the program's spans on, and read it by
the program's scopes and steps.

    python3 bench/trace_scopes.py --workload <name> --seed <n> --seconds <s>
        [--tiny] [--keep <prefix>] [--cpu]

from the root of a checkout, on a TPU.  Set-up and the window are the
harness's (``bench/harness.py``: the same seed draws, warm-up and
closed loop), with the program's tracing on in the window, so its
``engine.*`` spans reach the profile.  After the window the compiled
call program's text is joined with the trace (``bench/scopes.py``).
The last line of standard output is one JSON object: the device
seconds by scope and by step, the costliest ops and longest idle gaps
with their scopes and spans, the ``engine.*`` spans' counts and
medians, and the readings of the per-layer readers that read a trace,
``permute_share``, ``gemm_roofline`` and ``launch_ms`` among them.
Nothing is checked against the reference: ``bench/run.py`` does that.

``--tiny`` runs the cell's configuration at the benchmark's CPU test
size (``bench/tests/_tiny.py``); ``--keep`` leaves the profile and the
program's text at ``<prefix>.xplane.pb.gz`` and ``<prefix>.hlo.txt.gz``
(``bench/tests/data/`` holds one such pair); ``--cpu`` runs on JAX's CPU
backend, where a trace has no device ops, to rehearse the script.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the readers of a traced window, the harness's and this script's
READERS = ("transpose_share", "slice_roofline", "device_idle_share",
           "permute_share", "gemm_roofline", "launch_ms")


def trace_cell(workload, seed, seconds, *, tiny=False, keep=None,
               require_tpu=True) -> dict:
    from bench import circuits, harness, scopes, system

    if tiny:
        from bench.tests._tiny import tiny_spec

        spec = tiny_spec()
    else:
        spec = harness.load_spec()
    cell, cfg, mix, _ = harness.resolve(spec, workload)
    system.add_program(ROOT)
    system.enable_compile_cache()
    import jax
    from repro.obs.trace import enabled_scope

    device = harness.device_info(jax, cell["chips"], require_tpu)
    n = cfg["rows"] * cfg["cols"]
    job = system.Job(cfg, mix, circuits.make_circuit(cfg, seed),
                     harness.draw_bitstring(seed, n))
    per_call = mix["ids_per_call"]
    start = harness.first_id(seed, job.n_slices, per_call)
    job.hoist()
    job.call(harness.call_ids(start, -1, per_call, job.n_slices))

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    calls = 0
    t0 = time.perf_counter()
    with enabled_scope(True), jax.profiler.TraceAnnotation("bench.window"):
        while True:
            ids = harness.call_ids(start, calls, per_call, job.n_slices)
            with jax.profiler.TraceAnnotation("bench.call"):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    res = job.dispatch(ids)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(res)
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
    jax.profiler.stop_trace()
    hlo = job.session.compiled_slices(job.slice_batch).as_text()
    xplane = harness._find_xplane(trace_dir)
    summary = scopes.summarize(xplane, hlo)
    if keep is not None:
        with open(xplane, "rb") as f, \
                gzip.open(keep + ".xplane.pb.gz", "wb") as g:
            g.write(f.read())
        with gzip.open(keep + ".hlo.txt.gz", "wt") as g:
            g.write(hlo)
    shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = {
        "trace": summary, "slices_traced": calls * per_call,
        "chips": cell["chips"], "problem": job.problem(),
        "peaks": harness.peaks_for(
            device["kind"] if require_tpu else "TPU v5 lite"),
    }
    metrics = {}
    for name in READERS:
        path = os.path.join(ROOT, "bench", "metrics", name + ".py")
        v = harness.load_reader(path)(ctx)
        if v is not None:
            metrics[name] = v
    return {
        "workload": workload, "seed": seed, "tiny": tiny, "device": device,
        "slices_traced": ctx["slices_traced"], "metrics": metrics,
        "window_s": summary["window_s"], "busy_s": summary["busy_s"],
        "scopes": summary["scope_s"], "scope_ops": summary["scope_op_s"],
        "unmatched_s": summary["unmatched_s"], "steps": summary["step_s"],
        "device_ops": summary["top_ops"], "idle_gaps": summary["gaps"],
        "program_spans": {
            n: [len(d), statistics.median(d), max(d)]
            for n, d in summary["program_spans"].items()
        },
        "notes": ctx.get("notes", {}),
        "total_s": time.perf_counter() - T_START,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--keep")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    result = trace_cell(args.workload, args.seed, args.seconds,
                        tiny=args.tiny, keep=args.keep,
                        require_tpu=not args.cpu)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
