"""Run one benchmark cell once and print its result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``, a circuit recipe and its
planning options) under a traffic mix (``bench/mixes/<traffic>.json``).
Per-layer metrics are readers in ``bench/metrics/<metric>.py``; the
chip's peaks are in ``bench/peaks.json``.  Nothing here names a cell:
a new cell is new files and new entries.

One run:

1. set-up: the persistent compilation cache, the chip, the circuit and
   bitstring drawn from ``--seed``, planning and lowering, the leaves on
   the device, the hoisted prologue (timed once more on its own) and one
   warm-up call, which compiles the call program or loads it from the
   cache; each phase's seconds go into the result's ``run`` record;
2. the window: calls on consecutive slice ids from a first id drawn from
   the seed, each waited for before the next (closed loop, one caller),
   until ``--seconds`` have passed; with ``--trace 1`` under the
   profiler;
3. the device's memory peak (the runtime's counter, and the footprint of
   the compiled call program, which no call needs and so is read only
   now), then the check: a sample of the window's calls, drawn from the
   seed, against the plain reference (``bench/reference.py``);
4. the result line: the end-to-end metrics with ``--trace 0``, the
   per-layer metrics with ``--trace 1``.

``full_contraction_s`` = prologue + 2**|S| x (window / slices done).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# the duration event JAX records around each XLA backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class CompileClock:
    """Sums the backend compile time JAX reports, between two reads."""

    def __init__(self, jax):
        self.total = 0.0

        def listen(name, secs, **_):
            if name == COMPILE_EVENT:
                self.total += secs

        jax.monitoring.register_event_duration_secs_listener(listen)

    def lap(self) -> float:
        t, self.total = self.total, 0.0
        return t


class Phases:
    """Seconds of each phase of set-up, from the process's start."""

    def __init__(self, t_start: float):
        self.last = t_start
        self.seconds: dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = now - self.last
        self.last = now


# ----------------------------------------------------------------------
# the benchmark's files
# ----------------------------------------------------------------------
def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def resolve(spec: dict, workload: str, root: str = ROOT):
    """``(cell, config, mix, metrics)`` for one workload name; ``metrics``
    maps each per-layer metric the cell reports to its reader's file."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = _json(os.path.join(root, configs[cell["config"]]["file"]))
    mix = _json(os.path.join(HERE, "mixes", cell["traffic"] + ".json"))
    metrics = {}
    for m in spec["per_layer"]:
        if workload in m.get("workloads", [workload]):
            metrics[m["name"]] = os.path.join(HERE, "metrics",
                                              m["name"] + ".py")
    return cell, cfg, mix, metrics


def load_reader(path: str):
    name = "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device {kind!r} is not in bench/peaks.json")
    return table[kind]


# ----------------------------------------------------------------------
# what the seed draws
# ----------------------------------------------------------------------
def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), stream])


def draw_bitstring(seed: int, n: int) -> str:
    return "".join(str(b) for b in rng_for(seed, 1).integers(0, 2, n))


def first_id(seed: int, n_slices: int, per_call: int) -> int:
    start = int(rng_for(seed, 2).integers(0, n_slices))
    return start - start % per_call


def call_ids(start: int, j: int, per_call: int, n_slices: int) -> list:
    return [(start + j * per_call + i) % n_slices for i in range(per_call)]


# ----------------------------------------------------------------------
def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"JAX's default backend is {d.platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak(jax, chips: int, compiled_bytes: int) -> int:
    """The fullest chip's peak: the runtime's ``peak_bytes_in_use`` or
    the compiled call program's footprint, whichever is larger (on a
    v5e the runtime's counter leaves out a program's temporaries)."""
    peak = compiled_bytes
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def check_calls(ref, results, seed: int, n_check: int, limit: float):
    """Compare a seed-drawn sample of the window's calls with the
    reference.  Returns (worst relative error, calls checked, calls
    failed).  The error of a call is the largest gap between its result
    and the reference's sum, over the sum of the slices' magnitudes."""
    pick = rng_for(seed, 3).choice(
        len(results), size=min(n_check, len(results)), replace=False
    )
    worst, failed = 0.0, 0
    for j in sorted(int(p) for p in pick):
        ids, got = results[j]
        want, scale = ref.batch_sum(ids)
        got = np.asarray(got, np.complex128).reshape(-1)
        want = np.asarray(want).reshape(-1)
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            err = float("inf")
        else:
            err = float(np.max(np.abs(got - want))) / max(scale, 1e-300)
        worst = max(worst, err)
        failed += int(not err <= limit)
    return worst, len(pick), failed


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, spec: dict | None = None, root: str = ROOT,
             require_tpu: bool = True, out=sys.stdout, err=sys.stderr,
             mutate=None) -> dict:
    """One run of one cell; prints and returns the result dict.

    ``mutate(job)``, for the benchmark's own tests, may replace parts of
    the timed path after set-up."""
    from bench import circuits, system
    from bench.reference import Reference

    spec = spec if spec is not None else load_spec(root)
    cell, cfg, mix, readers = resolve(spec, workload, root)
    chips = cell["chips"]
    phases = Phases(t_start)
    system.add_program(root)
    system.enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    phases.mark("imports")
    device = device_info(jax, chips, require_tpu)
    clock = CompileClock(jax)
    phases.mark("device")

    n = cfg["rows"] * cfg["cols"]
    ops = circuits.make_circuit(cfg, seed)
    bits = draw_bitstring(seed, n)
    phases.mark("circuit")
    job = system.Job(cfg, mix, ops, bits)
    per_call = mix["ids_per_call"]
    start = first_id(seed, job.n_slices, per_call)
    phases.mark("plan_and_leaves")
    prologue_s = job.hoist()
    phases.mark("hoist")
    job.call(call_ids(start, -1, per_call, job.n_slices))  # warm-up
    if mutate is not None:
        mutate(job)
    compile_s = clock.lap()
    phases.mark("warm_up")

    results = []
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        phases.mark("profiler")
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        j = 0
        while True:
            ids = call_ids(start, j, per_call, job.n_slices)
            with jax.profiler.TraceAnnotation("bench.call"):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    res = job.dispatch(ids)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    res = np.asarray(jax.block_until_ready(res))
            results.append((ids, res))
            j += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    window_compile_s = clock.lap()
    slices = len(results) * per_call
    mem_peak = memory_peak(jax, chips, job.compiled_bytes())

    problem = job.problem()
    plan_s = job.plan_s
    n_slices = job.n_slices
    job.close()
    del job
    gc.collect()

    labels, arrays, _ = circuits.network(cfg, ops, bits,
                                         range(n - mix["open_qubits"], n))
    ref = Reference(problem, labels, arrays)
    limit = cfg["check"]["rel_err_limit"]
    worst, checked, failed = check_calls(
        ref, results, seed, mix["check_calls"], limit
    )
    correct = failed == 0 and checked > 0
    checks = {
        "rel_err": {"value": worst, "limit": limit},
        "calls_checked": {"value": checked, "limit": 1},
    }
    result = {"correct": correct, "attempted": len(results),
              "failed": failed}
    device["memory_peak_bytes"] = mem_peak
    if trace:
        from bench.trace_reduce import summarize

        summary = summarize(_find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {
            "plan_s": plan_s, "compile_s": compile_s, "problem": problem,
            "peaks": peaks_for(
                device["kind"] if require_tpu else "TPU v5 lite"),
            "trace": summary, "slices_traced": slices, "chips": chips,
        }
        metrics = {}
        for name, path in readers.items():
            v = load_reader(path)(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": _unit(spec, name)}
        device["busy_s"] = sum(summary["busy_s"]) / max(
            1, len(summary["busy_s"]))
        device["window_s"] = summary["window_s"]
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": summary["top_ops"],
                               "idle_gaps": summary["gaps"]}
    else:
        full = prologue_s + n_slices * window_s / slices
        result["metrics"] = {
            "full_contraction_s": {"value": full, "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["device"] = device
    result["run"] = {
        "slices_done": slices, "n_slices": n_slices, "window_s": window_s,
        "prologue_s": prologue_s, "compile_s_in_window": window_compile_s,
        "setup_s": setup_s, "setup_phases": phases.seconds,
    }
    if trace:
        result["run"].update(ctx.get("notes", {}))
    result["checks"] = checks
    print(f"setup phases {json.dumps(phases.seconds)}", file=err)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    print(json.dumps(result), file=out, flush=True)
    return result


def _unit(spec: dict, name: str) -> str:
    for m in spec["per_layer"] + spec["end_to_end"]:
        if m["name"] == name:
            return m["unit"]
    raise KeyError(name)


def _find_xplane(d: str) -> str:
    for dirpath, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {d}")
