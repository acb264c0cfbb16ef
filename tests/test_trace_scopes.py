"""Named scopes in the slice program, span records on the profiler's
clock, and the benchmark's join of a device trace with the program's
text.

* The slice program names each contraction step and its parts
  (``step<k>.<backend>``, ``permute``, ``gemm``; ``repro.core.executor``)
  in op metadata only: its optimized HLO is the same without the scopes.
* A span's record and its ``TraceAnnotation`` in an XLA profile start at
  the same time on one clock.
* ``bench/scopes.py`` and the readers ``permute_share``,
  ``gemm_roofline`` and ``launch_ms`` read a small trace recorded on one
  v5e (``bench/tests/data/tiny-syc.amp.*``: ``bench/trace_scopes.py
  --workload syc30.amp --seed 2147483701 --seconds 0.1 --tiny --keep
  bench/tests/data/tiny-syc.amp``).
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core.api import open_session
from repro.obs import trace
from repro.quantum.circuits import sycamore_like

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, scopes  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")
TINY_TRACE = os.path.join(DATA, "tiny-syc.amp.xplane.pb.gz")
TINY_HLO = os.path.join(DATA, "tiny-syc.amp.hlo.txt.gz")
SMALL_TRACE = os.path.join(DATA, "small.xplane.pb.gz")
READERS = ("permute_share", "gemm_roofline", "launch_ms")


# ----------------------------------------------------------------------
# the scopes in the slice program
# ----------------------------------------------------------------------
def _tiny_session():
    """A 12-qubit Sycamore-recipe amplitude at the benchmark's CPU test
    size (``bench/tests/data/tiny-syc.json``), on the gemm backend."""
    circ = sycamore_like(3, 4, 8)
    sess, _ = open_session(
        circ, "0" * circ.num_qubits, target_dim=7, backend="gemm",
        slicing_mode="peak", use_cache=False,
    )
    sess.hoisted()
    return sess


def _lowered(sess):
    sess.plan._compiled.clear()
    return sess._batch_fn().lower(
        list(sess.arrays), list(sess.hoisted()),
        jax.ShapeDtypeStruct((2, sess.plan.num_sliced), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.bool_),
    )


def _code(hlo_text: str) -> list[str]:
    """HLO text without metadata and without the stack-frame tables that
    metadata points into."""
    text = re.sub(r", metadata=\{[^}]*\}", "", hlo_text)
    return [
        line for line in text.splitlines()
        if not re.match(r"(\d+ |FileNames|FunctionNames|FileLocations|"
                        r"StackFrames)", line)
    ]


@pytest.fixture(scope="module")
def tiny_session():
    return _tiny_session()


def test_every_epilogue_step_names_its_permute_and_gemm(tiny_session):
    plan = tiny_session.plan
    text = _lowered(tiny_session).as_text(debug_info=True)
    assert plan.epilogue_idx
    for k in plan.epilogue_idx:
        backend = plan.schedule.specs[k].backend
        for part in ("permute", "gemm"):
            assert re.search(
                rf'"[^"]*\bstep{k}\.{backend}\)?/{part}/', text
            ), (k, part)
    for scope in ("leaves", "output", "batch_sum"):
        assert re.search(rf'"[^"]*\b{scope}\)?/', text), scope


def test_scopes_change_metadata_only(tiny_session, monkeypatch):
    scoped = _lowered(tiny_session).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lowered(tiny_session).compile().as_text()
    assert "/permute/" in scoped and "/permute/" not in bare
    assert _code(scoped) == _code(bare)


# ----------------------------------------------------------------------
# span records on the profiler's clock
# ----------------------------------------------------------------------
def test_span_record_and_annotation_share_one_clock(tmp_path):
    from jax.profiler import ProfileData

    name = "clock.probe"
    trace.reset()
    with trace.enabled_scope(True):
        jax.profiler.start_trace(str(tmp_path / "profile"))
        try:
            with trace.span(name):
                jnp.ones(8).block_until_ready()
        finally:
            jax.profiler.stop_trace()
    trace.dump_trace(str(tmp_path / "spans.jsonl"))
    with open(tmp_path / "spans.jsonl") as f:
        rec = [e for e in map(json.loads, f) if e["name"] == name][0]
    trace.reset()

    path = glob.glob(str(tmp_path / "profile" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    env = pd.find_plane_with_name("Task Environment")
    start = dict((str(k), v) for k, v in env.stats)["profile_start_time"]
    ann = [ev for p in pd.planes if p.name.startswith("/host:")
           for ln in p.lines for ev in ln.events if ev.name == name]
    assert len(ann) == 1
    # event times are offsets from profile_start_time, in nanoseconds;
    # the record's ts is in microseconds
    assert abs(rec["ts"] * 1e3 - (start + ann[0].start_ns)) < 1e6
    assert abs(rec["dur"] * 1e3 - ann[0].duration_ns) < 1e6


# ----------------------------------------------------------------------
# the join, on a trace recorded on the chip
# ----------------------------------------------------------------------
@pytest.mark.parametrize("op_name,want", [
    ("jit(fn)/vmap(step45.pallas)/gemm/jit(tiled_matmul)/tiled_matmul/"
     "pallas_call", ("gemm", "45", "pallas")),
    ("jit(fn)/vmap(step3.einsum)/permute/transpose", ("permute", "3",
                                                       "einsum")),
    ("jit(fn)/vmap(step8.dot)/permute/reshape;"
     "jit(fn)/vmap(step6.dot)/gemm/reshape", ("permute", "8", "dot")),
    ("jit(fn)/vmap(leaves)/shift_right_arithmetic", ("leaves", "", "")),
    ("jit(fn)/batch_sum/jit(_where)/select_n", ("batch_sum", "", "")),
    ("jit(fn)/vmap(output)/reshape", ("output", "", "")),
    ("jit(<lambda>)/prologue/step2.dot/gemm/dot_general",
     ("gemm", "2", "dot")),
    ("jit(gemm)/dot_general", ("unscoped", "", "")),
    ("arrs[5]", ("unscoped", "", "")),
    ("", ("unscoped", "", "")),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_op_names_follow_operands_and_find_arguments():
    text = "\n".join([
        "HloModule m",
        "%fused (p: f32[2]) -> f32[2] {",
        "  %p = f32[2]{0} parameter(0)",
        '  ROOT %t = f32[2]{0} transpose(%p), metadata={op_name="a/b"}',
        "}",
        "ENTRY %main (x.1: f32[2]) -> f32[2] {",
        '  %x.1 = f32[2]{0} parameter(0), metadata={op_name="arrs[3]"}',
        "  %copy-start = (f32[2]{0}, u32[]) copy-start(%x.1)",
        "  %copy-done = f32[2]{0} copy-done(%copy-start)",
        "  ROOT %fusion.2 = f32[2]{0} fusion(%copy-done), calls=%fused, "
        'metadata={op_name="jit(f)/vmap(step1.dot)/gemm/dot_general" '
        "stack_frame_id=3}",
        "}",
    ])
    names = scopes.op_names(text)
    assert names["copy-done"] == names["copy-start"] == "arrs[3]"
    assert names["fusion.2"].startswith("jit(f)/vmap(step1.dot)/gemm/")
    assert names["p"] == ""
    assert scopes.arguments(text) == {"arrs[3]"}


@pytest.fixture(scope="module")
def tiny_trace():
    with gzip.open(TINY_HLO, "rt") as f:
        hlo = f.read()
    return hlo, scopes.summarize(TINY_TRACE, hlo)


def test_recorded_trace_is_all_scoped(tiny_trace):
    hlo, s = tiny_trace
    busy = sum(s["busy_s"])
    assert busy > 0 and s["unmatched_s"] == 0
    assert s["scope_s"].get("unscoped", 0.0) < 0.05 * busy
    assert sum(s["scope_s"].values()) == pytest.approx(
        sum(s["op_s"].values()))
    for sc in ("permute", "gemm", "leaves", "batch_sum"):
        assert s["scope_s"][sc] > 0, sc
    # every kernel and every dot of the program sits in a gemm scope
    names, args = scopes.op_names(hlo), scopes.arguments(hlo)
    kernels = [n for n, on in names.items() if "pallas_call" in on]
    assert all(scopes.scope_of(names[n])[0] == "gemm" for n in kernels)
    matmul = s["op_s"].get("matmul", 0.0)
    assert matmul > 0
    assert s["scope_op_s"]["gemm"]["matmul"] == pytest.approx(matmul)
    assert args and all(a.startswith(("arrs[", "hbufs[", "ids_", "valid_"))
                        for a in args)


def test_recorded_trace_names_steps_ops_and_gaps(tiny_trace):
    _, s = tiny_trace
    assert 0 < len(s["step_s"]) <= 10
    for row in s["step_s"]:
        assert row["backend"] == "einsum"
        assert 0 < row["gemm_s"] + row["permute_s"] <= row["s"] + 1e-12
    assert all(" @" in n for n, _ in s["top_ops"])
    assert {g[0] for g in s["gaps"]} <= {
        "engine.ids_put", "engine.launch", "engine.run_slices",
        "bench.dispatch", "bench.wait", "bench.call",
    }
    assert any(g[0].startswith("engine.") for g in s["gaps"])
    spans = s["program_spans"]
    assert len(spans["engine.run_slices"]) == len(spans["engine.launch"])
    # the join leaves the harness's own summary as it was
    base = tr.summarize(TINY_TRACE)
    for key in ("window_s", "busy_s", "op_s"):
        assert s[key] == base[key]


@pytest.fixture(scope="module")
def tiny_problem():
    from bench import circuits, system

    cfg = harness._json(os.path.join(DATA, "tiny-syc.json"))
    mix = harness._json(os.path.join(ROOT, "bench", "mixes", "amp.json"))
    n = cfg["rows"] * cfg["cols"]
    job = system.Job(cfg, mix, circuits.make_circuit(cfg, 2147483701),
                     harness.draw_bitstring(2147483701, n))
    return job.problem(), mix["ids_per_call"]


def _ctx(summary, problem, per_call):
    calls = [sp for sp in scopes.host_spans(tr.load(TINY_TRACE))
             if sp[0] == "bench.call"]
    return {"trace": summary, "problem": problem, "chips": 1,
            "slices_traced": len(calls) * per_call,
            "peaks": harness.peaks_for("TPU v5 lite")}


@pytest.mark.parametrize("metric", READERS)
def test_readers_on_the_recorded_trace(tiny_trace, tiny_problem, metric):
    ctx = _ctx(tiny_trace[1], *tiny_problem)
    read = harness.load_reader(
        os.path.join(ROOT, "bench", "metrics", metric + ".py"))
    assert 0 < read(ctx) <= 100


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_nothing_without_the_join(tiny_problem, metric):
    # a trace summarized without the program's text, as of a program
    # that names no scopes and opens no engine spans
    ctx = _ctx(tr.summarize(SMALL_TRACE), *tiny_problem)
    read = harness.load_reader(
        os.path.join(ROOT, "bench", "metrics", metric + ".py"))
    assert read(ctx) is None


def test_gemm_roofline_refuses_a_kernel_outside_gemm(tiny_trace,
                                                     tiny_problem):
    s = json.loads(json.dumps(tiny_trace[1]))
    matmul = s["op_s"]["matmul"]
    s["scope_op_s"]["gemm"]["matmul"] = 0.9 * matmul
    ctx = _ctx(s, *tiny_problem)
    read = harness.load_reader(
        os.path.join(ROOT, "bench", "metrics", "gemm_roofline.py"))
    assert read(ctx) is None
    assert "outside gemm" in ctx["notes"]["gemm_roofline_none"]
