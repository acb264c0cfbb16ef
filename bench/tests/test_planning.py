"""The benchmark's circuits, its Eq. 4 and its plans, at full size.

Planning runs on the host, so these hold at the cells' own sizes."""

import copy

import numpy as np
import pytest

from bench import circuits, harness, system
from bench.metrics.slicing_overhead import overhead

SPEC = harness.load_spec()
CELLS = {w["config"]: w["name"] for w in SPEC["workloads"]}
SEEDS = (0, 7, 2**31 + 11)


def _job(cell, seed, use_cache=True):
    _, cfg, mix, _ = harness.resolve(SPEC, cell)
    cfg = copy.deepcopy(cfg)
    cfg["plan"]["use_cache"] = use_cache
    mix = dict(mix, devices=1)
    n = cfg["rows"] * cfg["cols"]
    ops = circuits.make_circuit(cfg, seed)
    return system.Job(cfg, mix, ops, harness.draw_bitstring(seed, n)), cfg


@pytest.mark.parametrize("config", sorted(CELLS))
def test_circuit_is_the_programs_recipe(config):
    from repro.quantum import circuits as prog

    _, cfg, _, _ = harness.resolve(SPEC, CELLS[config])
    gen = {"sycamore": prog.sycamore_like}[cfg["recipe"]]
    for seed in SEEDS:
        want = gen(cfg["rows"], cfg["cols"], cfg["cycles"], seed=seed)
        got = circuits.make_circuit(cfg, seed)
        assert [(op.name, op.qubits) for op in want.ops] == got


def test_gate_matrices_are_the_programs():
    from repro.quantum import gates

    for name, m in circuits.SINGLE_QUBIT.items():
        np.testing.assert_allclose(gates.gate_array(name), m, atol=1e-7)
    cfg = harness.resolve(SPEC, "syc30.amp")[1]
    np.testing.assert_allclose(
        gates.gate_array("syc"), circuits.gate_matrix(cfg, "syc"), atol=1e-7
    )


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_eq4_copy_matches_the_program(cell):
    job, _ = _job(cell, 3)
    plan = job.plan
    assert overhead(job.problem()) == pytest.approx(
        plan.tree.slicing_overhead(plan.smask), rel=1e-12
    )


@pytest.mark.parametrize("config", sorted(CELLS))
def test_every_seed_gives_the_same_plan(config):
    problems = [_job(CELLS[config], s, use_cache=False)[0].problem()
                for s in SEEDS]
    assert all(p == problems[0] for p in problems[1:])
    assert len(problems[0]["sliced"]) >= 10


def test_roofline_work_is_below_the_naive_count():
    from bench.metrics.slice_roofline import slice_work
    from bench.tree import node_sets

    job, _ = _job("syc30.amp", 3)
    p = job.problem()
    w = slice_work(p, harness.peaks_for("TPU v5 lite")["vmem_bytes"])
    _, steps = node_sets(p)
    s = frozenset(p["sliced"])
    every = 8 * sum(2.0 ** len(u - s) for *_, u in steps)
    assert 0 < w["flops"] <= every
    assert w["bytes"] > 0
