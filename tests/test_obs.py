"""Observability layer: off-path contract, span integrity, metrics,
cache counters, logging, calibration.

The load-bearing guarantee is the off path: with ``REPRO_TRACE=0`` (the
default) tracing must be no-op stubs — results bitwise-identical, plan
fingerprints unchanged, no spans recorded.  With tracing on, span trees
must be well-formed (properly nested, non-overlapping per thread) and
the metrics counters must agree with the caches' own ``stats()``.
"""

from __future__ import annotations

import json
import subprocess
import sys
import threading

import numpy as np
import pytest

from conftest import subprocess_kwargs

import repro.obs as obs
from repro.obs import log as obs_log, metrics, trace


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Save/restore the process-global tracing flag and wipe recorded
    telemetry around every test — CI runs this module under both
    REPRO_TRACE=0 and =1, so tests must not assume the env default."""
    prev = trace.enabled()
    obs.reset()
    yield
    trace.set_enabled(prev)
    obs.reset()


def _contract_setup(backend="gemm", seed=0):
    from repro.core.api import plan_compiled
    from repro.core.executor import simplify_network
    from repro.quantum.circuits import circuit_to_network, sycamore_like

    c = sycamore_like(3, 3, 8, seed=seed)
    tn, arrays = circuit_to_network(c, bitstring="0" * 9)
    tn, arrays = simplify_network(tn, arrays)
    plan, report = plan_compiled(tn, 6, backend=backend, use_cache=False)
    return plan, report, arrays


# ----------------------------------------------------------------------
# off-path contract
# ----------------------------------------------------------------------
def test_off_path_is_noop_stub():
    trace.set_enabled(False)
    s = trace.span("anything", key="value")
    assert s is trace._NOOP  # shared stub, no allocation per call
    with s:
        pass
    metrics.inc("should.not.exist")
    metrics.observe("should.not.exist.h", 1.0)
    assert trace.get_spans() == []
    snap = metrics.snapshot()
    assert "should.not.exist" not in snap["counters"]
    assert "should.not.exist.h" not in snap["histograms"]


def test_off_path_results_bitwise_equal():
    plan, _, arrays = _contract_setup()
    trace.set_enabled(False)
    off = np.asarray(plan.contract_all(arrays, slice_batch=4))
    trace.set_enabled(True)
    on = np.asarray(plan.contract_all(arrays, slice_batch=4))
    trace.set_enabled(False)
    again = np.asarray(plan.contract_all(arrays, slice_batch=4))
    # bitwise, not allclose: the traced path must run the identical
    # compiled artifact
    assert off.tobytes() == on.tobytes()
    assert off.tobytes() == again.tobytes()


def test_plan_fingerprint_unchanged_by_telemetry():
    """The telemetry toggle must not join the plan-cache key: a traced
    call hits the entry a non-traced call planted, and vice versa."""
    from repro.core.api import plan_compiled
    from repro.quantum.circuits import circuit_to_network, sycamore_like

    c = sycamore_like(3, 3, 6, seed=3)
    tn, _ = circuit_to_network(c, bitstring="0" * 9)
    plan_a, rep_a = plan_compiled(tn, 6, telemetry=False)
    plan_b, rep_b = plan_compiled(tn, 6, telemetry=True)
    assert plan_b is plan_a  # same cached object == same fingerprint
    assert rep_b.cache_hit
    assert rep_a.telemetry is None
    assert rep_b.telemetry is not None


def test_telemetry_report_through_api(small_circuit):
    from repro.core.api import simulate_amplitude

    n = small_circuit.num_qubits
    r_off = simulate_amplitude(
        small_circuit, "0" * n, target_dim=8, telemetry=False
    )
    r_on = simulate_amplitude(
        small_circuit, "0" * n, target_dim=8, telemetry=True
    )
    assert r_off.report.telemetry is None
    t = r_on.report.telemetry
    assert np.asarray(r_off.value).tobytes() == np.asarray(
        r_on.value
    ).tobytes()
    assert "exec.contract_all" in t["spans"]
    assert t["metrics"]["counters"]["exec.slices_executed"] >= 1


# ----------------------------------------------------------------------
# span integrity
# ----------------------------------------------------------------------
def _check_well_formed(spans):
    """Per thread: spans properly nested, siblings non-overlapping."""
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        assert s.t_end >= s.t_start
        if s.parent_id:
            p = by_id[s.parent_id]
            assert p.thread == s.thread
            assert p.t_start <= s.t_start and s.t_end <= p.t_end
    from collections import defaultdict

    children = defaultdict(list)
    for s in spans:
        children[(s.thread, s.parent_id)].append(s)
    for sibs in children.values():
        sibs.sort(key=lambda s: s.t_start)
        for a, b in zip(sibs, sibs[1:]):
            assert a.t_end <= b.t_start  # non-overlapping


def test_span_tree_well_formed_nested():
    trace.set_enabled(True)
    with trace.span("outer"):
        with trace.span("mid"):
            with trace.span("inner"):
                pass
        with trace.span("mid2"):
            pass
    spans = trace.get_spans()
    assert [s.name for s in spans] == ["inner", "mid", "mid2", "outer"]
    _check_well_formed(spans)
    outer = spans[-1]
    assert outer.parent_id == 0
    assert {s.parent_id for s in spans if s.name.startswith("mid")} == {
        outer.span_id
    }


def test_span_stacks_are_thread_local():
    trace.set_enabled(True)
    # all threads alive at once: OS thread ids are reused otherwise
    barrier = threading.Barrier(4)

    def work(tag):
        barrier.wait()
        with trace.span(f"t-{tag}"):
            with trace.span(f"t-{tag}-child"):
                pass
        barrier.wait()

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = trace.get_spans()
    assert len(spans) == 8
    _check_well_formed(spans)
    # every top-level span sits on its own thread
    tops = [s for s in spans if s.parent_id == 0]
    assert len(tops) == 4
    assert len({s.thread for s in tops}) == 4


def test_span_trees_agree_scan_vs_resumable():
    from repro.core.distributed import contract_resumable

    plan, _, arrays = _contract_setup(seed=1)
    trace.set_enabled(True)
    scan_val = np.asarray(plan.contract_all(arrays, slice_batch=2))
    scan_spans = {s.name for s in trace.get_spans()}
    obs.reset()
    res_val, _state = contract_resumable(plan, arrays, chunk=2)
    res_spans = {s.name for s in trace.get_spans()}
    _check_well_formed(trace.get_spans())
    assert np.allclose(scan_val, np.asarray(res_val))
    assert "exec.contract_all" in scan_spans
    assert "exec.resumable" in res_spans
    if plan.num_sliced:
        assert "exec.slice_range" in res_spans
    # both paths report the same executed-slice count
    n = 1 << plan.num_sliced
    assert (
        metrics.snapshot()["counters"]["exec.slices_executed"] == n
    )


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
def test_metrics_snapshot_reset_roundtrip():
    trace.set_enabled(True)
    metrics.inc("a.count")
    metrics.inc("a.count", 2)
    metrics.set_gauge("b.gauge", 7.5)
    metrics.observe("c.hist", 1.0)
    metrics.observe("c.hist", 3.0)
    snap = metrics.snapshot()
    assert snap["counters"]["a.count"] == 3
    assert snap["gauges"]["b.gauge"] == 7.5
    h = snap["histograms"]["c.hist"]
    assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 3.0
    assert h["mean"] == 2.0
    json.dumps(snap)  # snapshot must be JSON-serializable
    metrics.reset()
    empty = metrics.snapshot()
    assert empty == {"counters": {}, "gauges": {}, "histograms": {}}


def test_metrics_labeled_series_and_cardinality_cap():
    """Per-request labels materialize as ``name{label}`` series, but the
    registry caps distinct labels per base name — the overflow collapses
    into ``{_other}`` so request-keyed labels cannot grow a snapshot
    without bound."""
    reg = metrics.Registry(max_labels=3)
    for fam in ("fam-a", "fam-b", "fam-c"):
        reg.counter("serve.family_requests", label=fam).inc()
    # beyond the cap: new labels all collapse into the overflow series
    for fam in ("fam-d", "fam-e", "fam-f", "fam-g"):
        reg.counter("serve.family_requests", label=fam).inc()
    # an already-admitted label keeps its own series
    reg.counter("serve.family_requests", label="fam-a").inc()
    snap = reg.snapshot()["counters"]
    assert snap["serve.family_requests{fam-a}"] == 2
    assert snap["serve.family_requests{fam-b}"] == 1
    assert snap[f"serve.family_requests{{{metrics.OVERFLOW_LABEL}}}"] == 4
    assert "serve.family_requests{fam-d}" not in snap
    # the cap is per base name, not global
    reg.counter("other.series", label="fam-z").inc()
    assert "other.series{fam-z}" in reg.snapshot()["counters"]
    # unlabeled helpers keep the plain name
    assert reg.labeled("plain", None) == "plain"
    reg.reset()
    assert reg.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}
    }
    # reset clears the label ledger too: fam-d can be admitted now
    reg.counter("serve.family_requests", label="fam-d").inc()
    assert (
        "serve.family_requests{fam-d}" in reg.snapshot()["counters"]
    )


def test_metrics_snapshot_consistent_under_concurrent_writers():
    """A snapshot is a point-in-time view: with writer threads
    mid-flight, a histogram's (count, total, min, max, mean) must never
    be torn and counter totals must never be lost.  Every observation is
    the constant V, so any consistent snapshot satisfies
    ``total == count * V`` exactly — a torn read breaks the identity."""
    reg = metrics.Registry()
    V = 0.5  # exactly representable: count * V has no rounding slack
    stop = threading.Event()
    PER_THREAD, N_WRITERS = 4000, 4

    def writer():
        h = reg.histogram("w.hist")
        c = reg.counter("w.count")
        for _ in range(PER_THREAD):
            h.observe(V)
            c.inc()

    writers = [
        threading.Thread(target=writer) for _ in range(N_WRITERS)
    ]
    torn = []

    def reader():
        while not stop.is_set():
            snap = reg.snapshot()
            h = snap["histograms"].get("w.hist")
            if h is None or h["count"] == 0:
                continue
            if h["total"] != h["count"] * V:
                torn.append(h)
            if h["mean"] != V or h["min"] != V or h["max"] != V:
                torn.append(h)

    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in writers + readers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not torn
    snap = reg.snapshot()
    total = N_WRITERS * PER_THREAD
    assert snap["counters"]["w.count"] == total  # no lost increments
    assert snap["histograms"]["w.hist"]["count"] == total


def test_cache_counters_match_plan_cache_stats():
    from repro.lowering.cache import PlanCache, PlanEntry

    trace.set_enabled(True)
    cache = PlanCache(maxsize=4)
    cache.get("missing")
    cache.put("k", PlanEntry(None, None))
    cache.get("k")
    cache.get("k")
    stats = cache.stats()
    snap = metrics.snapshot()["counters"]
    assert stats["hits"] == 2 and stats["misses"] == 1
    assert snap["plan_cache.hits"] == stats["hits"]
    assert snap["plan_cache.misses"] == stats["misses"]


def test_hoist_cache_eviction_counters_match_stats():
    from repro.lowering.cache import HoistCache

    trace.set_enabled(True)
    cache = HoistCache(maxsize=8, max_bytes=100)
    a = np.zeros(10, np.float64)  # 80 bytes per entry
    cache.put("k1", ((a,), ()))
    cache.put("k2", ((a,), ()))  # over max_bytes -> evicts k1
    assert cache.get("k1") is None
    assert cache.get("k2") is not None
    stats = cache.stats()
    snap = metrics.snapshot()["counters"]
    assert stats["evictions"] == 1
    assert stats["evicted_bytes"] == 80
    assert snap["hoist_cache.evictions"] == stats["evictions"]
    assert snap["hoist_cache.evicted_bytes"] == stats["evicted_bytes"]
    assert snap["hoist_cache.hits"] == stats["hits"]
    assert snap["hoist_cache.misses"] == stats["misses"]


# ----------------------------------------------------------------------
# export / merge
# ----------------------------------------------------------------------
def test_dump_trace_jsonl_chrome_and_merge(tmp_path):
    trace.set_enabled(True)
    with trace.span("alpha", cat="test", answer=42):
        pass
    p1 = tmp_path / "t1.jsonl"
    n = trace.dump_trace(str(p1))
    assert n == 1
    ev = json.loads(p1.read_text().strip())
    assert ev["name"] == "alpha" and ev["ph"] == "X"
    assert ev["args"]["answer"] == 42
    pc = tmp_path / "t.chrome.json"
    trace.dump_trace(str(pc), fmt="chrome")
    wrapped = json.loads(pc.read_text())
    assert wrapped["traceEvents"][0]["name"] == "alpha"
    obs.reset()
    with trace.span("beta"):
        pass
    p2 = tmp_path / "t2.jsonl"
    trace.dump_trace(str(p2))
    merged = tmp_path / "merged.jsonl"
    total = trace.merge_traces([str(p1), str(p2)], str(merged))
    assert total == 2
    names = [
        json.loads(line)["name"]
        for line in merged.read_text().splitlines()
    ]
    assert sorted(names) == ["alpha", "beta"]
    with pytest.raises(ValueError):
        trace.dump_trace(str(p1), fmt="nope")


# ----------------------------------------------------------------------
# structured logging
# ----------------------------------------------------------------------
def test_log_level_filter_and_verbatim_stdout(capsys, monkeypatch):
    trace.set_enabled(False)  # stdout filtering must not depend on env
    monkeypatch.setenv("REPRO_LOG_LEVEL", "WARNING")
    obs_log.info("you should not see this")
    obs_log.warning("CACHED tag-1")
    out = capsys.readouterr().out
    # text printed verbatim (sweep-resume parser greps these lines)
    assert out == "CACHED tag-1\n"
    monkeypatch.setenv("REPRO_LOG_LEVEL", "DEBUG")
    obs_log.debug("now visible")
    assert capsys.readouterr().out == "now visible\n"
    # structured side-record rides on the trace as an instant event
    trace.set_enabled(True)
    obs_log.error("boom", code=3)
    recs = [s for s in trace.get_spans() if s.cat == "log"]
    assert len(recs) == 1
    assert recs[0].name == "boom"
    assert recs[0].attrs == {"level": "ERROR", "code": 3}


# ----------------------------------------------------------------------
# env gating
# ----------------------------------------------------------------------
def test_repro_trace_env_gating_subprocess():
    code = (
        "import repro.obs as obs\n"
        "with obs.span('s'):\n"
        "    pass\n"
        "print(len(obs.get_spans()))\n"
    )
    kw = subprocess_kwargs()
    for flag, expect in (("0", "0"), ("1", "1")):
        env = dict(kw["env"], REPRO_TRACE=flag, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-c", code],
            env=env, cwd=kw["cwd"], capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == expect
    env = dict(kw["env"], REPRO_TRACE="yes", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", "import repro.obs"],
        env=env, cwd=kw["cwd"], capture_output=True, text=True,
    )
    assert r.returncode != 0 and "REPRO_TRACE" in r.stderr


# ----------------------------------------------------------------------
# calibration
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["einsum", "gemm"])
def test_calibrate_plan_joins_model_and_measured(backend):
    plan, report, arrays = _contract_setup(backend=backend, seed=2)
    cal = obs.calibrate_plan(plan, arrays, repeat=1)
    assert cal.backend == plan.backend
    assert cal.num_steps == len(plan.steps)
    assert cal.peak_bytes == report.peak_bytes
    by_class = cal.ratio_by_class()
    assert by_class  # at least one backend class exercised
    # every class used by the plan appears with a finite positive ratio
    for cls, agg in by_class.items():
        assert agg["measured_s"] > 0.0
        assert agg["modeled_s"] > 0.0, cls
        assert np.isfinite(agg["ratio"]) and agg["ratio"] > 0.0
    if backend == "einsum":
        assert set(by_class) == {"einsum"}
    # every step covered exactly once
    assert len(cal.rows) == len(plan.steps)
    table = cal.table()
    assert "meas/model" in table and table.count("\n") >= 2
    json.dumps(cal.summary())
