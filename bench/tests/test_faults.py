"""A whole run at a small size on the CPU, with the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have, and true with nothing broken."""

import io
import os
import subprocess
import sys
import textwrap
import time

import pytest

from bench import circuits, harness
from bench.reference import Reference
from bench.tests._tiny import tiny_spec

SEED = 2**31 + 77


def _run(cell, mutate=None):
    out = io.StringIO()
    return harness.run_cell(
        cell, SEED, 0.5, False, t_start=time.perf_counter(),
        spec=tiny_spec(), require_tpu=False, out=out, err=io.StringIO(),
        mutate=mutate,
    )


def _altered(job):
    run = job.dispatch
    job.dispatch = lambda ids: run(ids) * (1 + 1e-3)


def _half(job):
    run = job.dispatch
    job.dispatch = lambda ids: run(ids[: len(ids) // 2]) * 2


def _control(job):
    spec = tiny_spec()
    _, cfg, mix, _ = harness.resolve(spec, "syc30.amp")
    n = cfg["rows"] * cfg["cols"]
    ops = circuits.make_circuit(cfg, SEED)
    labels, arrays, _ = circuits.network(
        cfg, ops, harness.draw_bitstring(SEED, n)
    )
    ref = Reference(job.problem(), labels, arrays)
    job.dispatch = lambda ids: ref.batch_sum(ids, "bf16x3")[0]


@pytest.mark.parametrize("cell", ["syc30.amp", "syc30.sample"])
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] and res["checks"]["calls_checked"]["value"] >= 1


@pytest.mark.parametrize("cell", ["syc30.amp", "syc30.sample"])
@pytest.mark.parametrize("fault", [_altered, _half])
def test_fault_is_not_correct(cell, fault):
    assert not _run(cell, fault)["correct"]


def test_control_in_the_programs_place_is_not_correct():
    res = _run("syc30.amp", _control)
    assert not res["correct"]


FOUR = textwrap.dedent("""
    import io, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    if {broken}:
        jax.lax.psum = lambda x, axis_name, **kw: x
    from bench import harness
    from bench.tests._tiny import tiny_spec
    res = harness.run_cell("syc30.amp.x4", {seed}, 0.5, False,
        t_start=time.perf_counter(), spec=tiny_spec(), require_tpu=False,
        out=io.StringIO(), err=io.StringIO())
    print("CORRECT", res["correct"])
""")


@pytest.mark.parametrize("broken", [False, True])
def test_four_chip_exchange(broken):
    code = FOUR.format(root=harness.ROOT,
                       src=os.path.join(harness.ROOT, "src"),
                       broken=broken, seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert f"CORRECT {not broken}" in p.stdout, p.stderr[-3000:]
