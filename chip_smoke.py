#!/usr/bin/env python3
"""Bring-up smoke test: the contraction engine's main path on a TPU v5e.

Run from the root of a checkout:

    python3 chip_smoke.py               # one chip: phases (a)-(d)
    python3 chip_smoke.py --four-chips  # four chips: phase (e) only

Phases, one JSON object per line on stdout:

  (a) device     — the default backend is a TPU v5e (the cost model's
                   constants are v5e's); anything else exits non-zero;
  (b) small      — ``simulate_amplitude`` and ``sample_bitstrings`` on
                   ``sycamore_like(4, 5, 12)`` at ``target_dim=18`` on
                   both backends, against a host statevector in numpy
                   complex128, to 1e-4 relative per amplitude;
  (c) real size  — ``sycamore_like(5, 6, 16)`` planned at
                   ``target_dim=26`` with peak slicing: a fixed set of
                   slice ids through the gemm plan's ``run_slices``,
                   checked against the einsum plan on the same ids under
                   ``jax.default_matmul_precision("highest")``, with the
                   compiled-vs-certified footprint and the measured peak;
  (d) service    — an ``EngineServer`` answers a cold and a warm burst
                   of mixed amplitude and sampling requests on the (b)
                   family, checked against the statevector;
  (e) four chips — ``ContractionSession.run_sharded`` over a 4-device
                   ``("data",)`` mesh on the (c) plan, against one
                   device's ``run_slices`` on the same ids, with every
                   device's ``peak_bytes_in_use``.

Compile seconds (the backend compile time JAX reports) and run seconds
are kept apart.  The last line is ``{"ok": true, "device": {...}}``; any
failed check raises, so the script exits non-zero and prints no such
line.  The persistent compilation cache follows
``repro.launch.compile_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

SMALL = dict(rows=4, cols=5, cycles=12, target_dim=18)
REAL = dict(rows=5, cols=6, cycles=16, target_dim=26)
REAL_IDS_PER_BATCH = 2
REAL_BATCHES = 4
RTOL = 1e-4
SHARDED_RTOL = 1e-5


# the duration event JAX records around each XLA backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


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


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def rel_err(got, want) -> float:
    got = np.asarray(got, np.complex128).reshape(-1)
    want = np.asarray(want, np.complex128).reshape(-1)
    return float(np.max(np.abs(got - want) / np.abs(want)))


# ----------------------------------------------------------------------
def phase_device(jax, want_count: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    out = {
        "phase": "a_device", "platform": d.platform, "kind": d.device_kind,
        "count": len(devs), "jax": jax.__version__,
    }
    if d.platform != "tpu":
        print(
            f"chip_smoke: no TPU — JAX's default backend is {d.platform!r}",
            file=sys.stderr,
        )
        raise SystemExit(1)
    kind = d.device_kind.lower()
    check("v5 lite" in kind or "v5e" in kind, f"not a v5e: {d.device_kind!r}")
    check(len(devs) >= want_count, f"need {want_count} devices")
    out["ok"] = True
    emit(out)
    return out


def phase_small(jax, clock) -> dict:
    from repro.core.api import sample_bitstrings, simulate_amplitude
    from repro.quantum import statevector
    from repro.quantum.circuits import sycamore_like

    circ = sycamore_like(SMALL["rows"], SMALL["cols"], SMALL["cycles"])
    n = circ.num_qubits
    t0 = time.perf_counter()
    psi = statevector.simulate(circ)
    oracle_s = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    bitstrings = ["".join(rng.choice(["0", "1"], n)) for _ in range(3)]
    out = {"phase": "b_small", "qubits": n, "oracle_s": oracle_s}
    for backend in ("einsum", "gemm"):
        clock.lap()
        walls, errs = [], []
        for bs in bitstrings:
            t0 = time.perf_counter()
            res = simulate_amplitude(
                circ, bs, target_dim=SMALL["target_dim"], backend=backend
            )
            walls.append(time.perf_counter() - t0)
            errs.append(rel_err(res.value, psi[tuple(map(int, bs))]))
        amp_compile = clock.lap()
        t0 = time.perf_counter()
        smp = sample_bitstrings(
            circ, num_samples=1000, target_dim=SMALL["target_dim"],
            backend=backend,
        )
        smp_cold = time.perf_counter() - t0
        smp_compile = clock.lap()
        t0 = time.perf_counter()
        sample_bitstrings(
            circ, num_samples=1000, target_dim=SMALL["target_dim"],
            backend=backend, seed=1,
        )
        smp_warm = time.perf_counter() - t0
        batch = smp.batch
        base = [int(c) for c in batch.base_bitstring]
        idx = tuple(
            slice(None) if q in batch.open_qubits else base[q]
            for q in range(n)
        )
        smp_err = rel_err(batch.amplitudes, psi[idx])
        out[backend] = {
            "report": res.report.row(),
            "amp_rel_err": max(errs),
            "amp_compile_s": amp_compile,
            "amp_cold_s": walls[0],
            "amp_warm_s": min(walls[1:]),
            "sample_rel_err": smp_err,
            "sample_xeb": float(smp.xeb),
            "sample_compile_s": smp_compile,
            "sample_cold_s": smp_cold,
            "sample_warm_s": smp_warm,
        }
        check(max(errs) <= RTOL, f"{backend} amplitude error {max(errs)}")
        check(smp_err <= RTOL, f"{backend} sampling error {smp_err}")
    out["ok"] = True
    emit(out)
    return out


def real_plan(backend: str):
    from repro.core.api import plan_compiled
    from repro.core.executor import simplify_network
    from repro.quantum.circuits import circuit_to_network, sycamore_like

    circ = sycamore_like(REAL["rows"], REAL["cols"], REAL["cycles"])
    tn, arrays = circuit_to_network(circ, bitstring="0" * circ.num_qubits)
    tn, arrays = simplify_network(tn, arrays)
    plan, report = plan_compiled(
        tn, REAL["target_dim"], dtype=arrays[0].dtype, backend=backend,
        slicing_mode="peak",
    )
    return plan, report, arrays


def real_ids(plan) -> list[int]:
    n_ids = REAL_IDS_PER_BATCH * REAL_BATCHES
    n_slices = 1 << plan.num_sliced
    half = n_ids // 2
    return sorted(set(range(half)) | set(range(n_slices - half, n_slices)))


def phase_real(jax, clock) -> dict:
    from repro.engine.session import ContractionSession

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    plan, report, arrays = real_plan("gemm")
    plan_s = time.perf_counter() - t0
    ids = real_ids(plan)
    sb = REAL_IDS_PER_BATCH
    batches = [ids[i:i + sb] for i in range(0, len(ids), sb)]
    sess = ContractionSession(plan, arrays)
    clock.lap()
    compiled = sess.compiled_slices(sb)
    compile_s = clock.lap()
    ma = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    mem = plan.memory_plan()
    certified = (
        mem.epilogue_peak(sb) if sess.hoist else sb * mem.peak_bytes
    )
    compiled_bytes = (
        ma.temp_size_in_bytes + ma.argument_size_in_bytes
        + ma.output_size_in_bytes
    )
    total = 0j
    batch_s = []
    for b in batches:
        t0 = time.perf_counter()
        part = complex(jax.block_until_ready(sess.run_slices(b)))
        batch_s.append(time.perf_counter() - t0)
        total += part
    run_compile_s = clock.lap()
    mem_stats = dev.memory_stats() or {}

    eplan, _, _ = real_plan("einsum")
    check(eplan.smask == plan.smask, "einsum and gemm plans slice apart")
    esess = ContractionSession(eplan, arrays)
    ref = 0j
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        for b in batches:
            ref += complex(jax.block_until_ready(esess.run_slices(b)))
    ref_s = time.perf_counter() - t0
    ref_compile_s = clock.lap()
    err = abs(total - ref) / abs(ref)
    warm = batch_s[1:]
    per_batch = float(np.mean(warm))
    out = {
        "phase": "c_real_size",
        "circuit": "sycamore_like(%d,%d,%d)" % (
            REAL["rows"], REAL["cols"], REAL["cycles"]
        ),
        "target_dim": REAL["target_dim"],
        "report": report.row(),
        "plan_s": plan_s,
        "num_sliced": plan.num_sliced,
        "slice_ids": ids,
        "slice_batch": sb,
        "compile_s": compile_s,
        "compile_s_at_first_run": run_compile_s,
        "batch_s": batch_s,
        "s_per_batch_after_warmup": per_batch,
        "extrapolated_all_slices_s": per_batch * (1 << plan.num_sliced) / sb,
        "tpu_custom_calls": kernels,
        "certified_peak_bytes": certified,
        "compiled_bytes": compiled_bytes,
        "compiled_temp_bytes": ma.temp_size_in_bytes,
        "compiled_to_certified": compiled_bytes / certified,
        "peak_bytes_in_use": mem_stats.get("peak_bytes_in_use"),
        "memory_stats": mem_stats,
        "partial_sum": [total.real, total.imag],
        "einsum_partial_sum": [ref.real, ref.imag],
        "rel_err_vs_einsum_highest": err,
        "einsum_s": ref_s,
        "einsum_compile_s": ref_compile_s,
    }
    check(kernels > 0, "no tpu_custom_call in the gemm program")
    check(err <= RTOL, f"gemm vs einsum error {err}")
    out["ok"] = True
    emit(out)
    return out


def phase_service(jax, clock) -> dict:
    from repro.engine import AmplitudeRequest, EngineServer, SampleRequest
    from repro.quantum import statevector
    from repro.quantum.circuits import sycamore_like

    circ = sycamore_like(SMALL["rows"], SMALL["cols"], SMALL["cycles"])
    n = circ.num_qubits
    psi = statevector.simulate(circ)
    td = SMALL["target_dim"]
    kw = {"backend": "gemm"}
    base = "0" * (n - 3)

    def burst(srv, tails, seed):
        amps = [
            srv.submit(AmplitudeRequest(circ, base + t, td, dict(kw)))
            for t in tails
        ]
        smp = srv.submit(
            SampleRequest(circ, num_samples=500, seed=seed, target_dim=td,
                          plan_kwargs=dict(kw))
        )
        t0 = time.perf_counter()
        vals = [t.result(timeout=600) for t in amps]
        res = smp.result(timeout=600)
        wall = time.perf_counter() - t0
        errs = [
            rel_err(v, psi[tuple(map(int, base + t))])
            for v, t in zip(vals, tails)
        ]
        check(all(t.status == "done" for t in amps + [smp]), "ticket lost")
        return {
            "wall_s": wall,
            "amp_rel_err": max(errs),
            "sample_xeb": float(res.xeb),
            "p50_total_s": float(np.median([t.total_s for t in amps])),
        }

    clock.lap()
    with EngineServer(max_batch=8, max_open=3) as srv:
        cold = burst(srv, ["000", "011", "101", "110"], seed=0)
        cold["compile_s"] = clock.lap()
        warm = burst(srv, ["001", "010", "100", "111"], seed=1)
        warm["compile_s"] = clock.lap()
        stats = srv.stats()
    out = {"phase": "d_service", "cold": cold, "warm": warm,
           "stats": stats}
    for b in (cold, warm):
        check(b["amp_rel_err"] <= RTOL, f"service error {b['amp_rel_err']}")
    check(stats["completed"] == 10 and stats["failed"] == 0,
          f"service stats {stats}")
    out["ok"] = True
    emit(out)
    return out


def phase_four_chips(jax, clock) -> dict:
    from repro.engine.session import ContractionSession
    from repro.launch.mesh import make_host_mesh

    devs = jax.devices()[:4]
    plan, report, arrays = real_plan("gemm")
    ids = real_ids(plan)
    sess = ContractionSession(plan, arrays)
    mesh = make_host_mesh((4,), ("data",))
    clock.lap()
    t0 = time.perf_counter()
    sharded = complex(jax.block_until_ready(
        sess.run_sharded(mesh, ("data",), slice_batch=1, slice_ids=ids)
    ))
    sharded_s = time.perf_counter() - t0
    sharded_compile_s = clock.lap()
    stats = [d.memory_stats() or {} for d in devs]
    peaks = [st.get("peak_bytes_in_use", 0) for st in stats]
    sb = REAL_IDS_PER_BATCH
    t0 = time.perf_counter()
    single = 0j
    for i in range(0, len(ids), sb):
        single += complex(jax.block_until_ready(
            sess.run_slices(ids[i:i + sb])
        ))
    single_s = time.perf_counter() - t0
    single_compile_s = clock.lap()
    err = abs(sharded - single) / abs(single)
    out = {
        "phase": "e_four_chips",
        "report": report.row(),
        "num_sliced": plan.num_sliced,
        "slice_ids": ids,
        "sharded_sum": [sharded.real, sharded.imag],
        "single_device_sum": [single.real, single.imag],
        "rel_err": err,
        "sharded_s": sharded_s,
        "sharded_compile_s": sharded_compile_s,
        "single_device_s": single_s,
        "single_device_compile_s": single_compile_s,
        "peak_bytes_in_use_per_device": peaks,
        "memory_stats_per_device": stats,
    }
    # the sum needs every device's share of the ids; the peaks show each
    # device held its own buffers, not device 0 alone
    check(err <= SHARDED_RTOL, f"sharded vs single-device error {err}")
    check(min(peaks) > 0 and min(peaks) >= max(peaks) // 2,
          f"uneven per-device peaks: {peaks}")
    out["ok"] = True
    emit(out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase (e), on four chips")
    args = ap.parse_args()
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    clock = CompileClock(jax)
    dev = phase_device(jax, 4 if args.four_chips else 1)
    emit({"phase": "compile_cache", "dir": cache_dir})
    if args.four_chips:
        phase_four_chips(jax, clock)
    else:
        phase_small(jax, clock)
        phase_real(jax, clock)
        phase_service(jax, clock)
    emit({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"],
    }})
    return 0


if __name__ == "__main__":
    sys.exit(main())
