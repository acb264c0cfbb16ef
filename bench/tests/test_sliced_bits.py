"""The reader ``sliced_bits`` gives the plan's |S|, the number the
program keeps as the gauge ``engine.sliced_bits``."""

import os

import pytest

from bench import circuits, harness, system

READER = os.path.join(harness.HERE, "metrics", "sliced_bits.py")


@pytest.mark.parametrize("traffic", ["amp", "sample-k6", "sample-k10"])
def test_reader_is_the_programs_gauge(traffic):
    from repro.obs import metrics, trace

    cfg = harness._json(os.path.join(harness.HERE, "tests", "data",
                                     "tiny-syc.json"))
    mix = harness._json(os.path.join(harness.HERE, "mixes",
                                     traffic + ".json"))
    mix = dict(mix, devices=1)
    n = cfg["rows"] * cfg["cols"]
    seed = 2**33 + 17
    prev = trace.enabled()
    trace.set_enabled(True)
    metrics.reset()
    try:
        job = system.Job(cfg, mix, circuits.make_circuit(cfg, seed),
                         harness.draw_bitstring(seed, n))
        gauge = metrics.snapshot()["gauges"]["engine.sliced_bits"]
    finally:
        trace.set_enabled(prev)
        metrics.reset()
    got = harness.load_reader(READER)({"problem": job.problem()})
    assert got == gauge == job.plan.num_sliced > 0
