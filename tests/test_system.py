"""End-to-end behaviour tests for the paper's system: the full
circuit → plan → slice → contract → XEB pipeline, with the paper's
headline claims checked at test scale."""

import numpy as np
import pytest

from repro.core import plan_contraction, simulate_amplitude, simplify_network
from repro.core.tensor_network import popcount
from repro.quantum import statevector, xeb
from repro.quantum.circuits import (
    circuit_to_network,
    random_1d_circuit,
    sycamore_like,
)


def test_full_pipeline_sycamore_like():
    """Plan + slice + contract a 4x4 sycamore-like circuit; the lifetime
    slicer must hit the memory bound with small overhead (paper: <1.2 on
    the real Sycamore network)."""
    circ = sycamore_like(4, 4, 10, seed=1)
    tn, arrays = circuit_to_network(circ, bitstring="0" * 16)
    tn, arrays = simplify_network(tn, arrays)
    target = 12
    tree, smask, report = plan_contraction(
        tn, target, method="lifetime", tune=True, merge=True
    )
    assert tree.sliced_width(smask) <= target
    assert report.slicing_overhead < 4.0  # small circuit; paper net: 1.255
    assert report.num_sliced >= 1


def test_xeb_validation_workflow():
    """Reproduce the paper's validation loop at test scale: simulate k
    sampled bitstring amplitudes with the sliced contraction engine and
    compute Linear XEB (Eq. 1)."""
    nq, k = 8, 24
    c = random_1d_circuit(nq, 8, seed=5)
    probs = statevector.probabilities(c)
    samples = xeb.sample_bitstrings(probs, k, seed=1)
    amp_probs = []
    for s in samples[:6]:  # budget: 6 amplitudes through the full engine
        bs = format(s, f"0{nq}b")
        res = simulate_amplitude(c, bs, target_dim=5, tune=False, merge=False)
        amp_probs.append(abs(complex(res.value)) ** 2)
    np.testing.assert_allclose(
        amp_probs, probs[samples[:6]], rtol=1e-3, atol=1e-6
    )
    f = xeb.linear_xeb(nq, probs[samples])
    assert f > 0.3  # sampled from the true distribution → positive XEB


def test_planner_improves_over_greedy_on_stemmy_network():
    """The paper's pipeline (lifetime slicing + tuning + merging) must not
    be worse than the greedy baseline on slicing overhead for a
    stem-dominant RQC network."""
    circ = sycamore_like(4, 5, 12, seed=2)
    tn, arrays = circuit_to_network(circ, bitstring="0" * 20)
    tn, _ = simplify_network(tn, arrays)
    target = 14
    _, s_greedy, rep_greedy = plan_contraction(
        tn, target, method="greedy", tune=False, merge=False, seed=0
    )
    _, s_life, rep_life = plan_contraction(
        tn, target, method="lifetime", tune=True, merge=False, seed=0
    )
    assert rep_life.slicing_overhead <= rep_greedy.slicing_overhead * 1.5
    assert popcount(s_life) <= popcount(s_greedy) + 1


# ---------------------------------------------------------------------
# planning options: one PlanOptions value behind every entry point
# ---------------------------------------------------------------------
def _entry_calls(circ):
    """Each public entry point, called on ``circ`` with extra options."""
    from repro.core import api

    n = circ.num_qubits
    bs = "0" * n
    tn, arrays = circuit_to_network(circ, bitstring=bs)
    tn, _ = simplify_network(tn, arrays)
    oq = (n - 2, n - 1)
    return {
        "plan_contraction": lambda **o: api.plan_contraction(tn, 4, **o),
        "plan_compiled": lambda **o: api.plan_compiled(tn, 4, **o),
        "simulate_amplitude": lambda **o: api.simulate_amplitude(
            circ, bs, target_dim=4, **o
        ),
        "open_amplitude_batch": lambda **o: api.open_amplitude_batch(
            circ, open_qubits=oq, target_dim=4, **o
        ),
        "sample_bitstrings": lambda **o: api.sample_bitstrings(
            circ, num_samples=8, open_qubits=oq, target_dim=4, **o
        ),
        "open_session": lambda **o: api.open_session(
            circ, bs, target_dim=4, **o
        ),
    }


def _spy_planners(monkeypatch):
    """Record the options each planner call receives."""
    import repro.optimize as optimize

    calls = []

    def spy(fn):
        def wrapper(*args, **kwargs):
            calls.append(kwargs)
            return fn(*args, **kwargs)

        return wrapper

    monkeypatch.setattr(optimize, "oneshot_plan", spy(optimize.oneshot_plan))
    monkeypatch.setattr(optimize, "plan_search", spy(optimize.plan_search))
    return calls


ENTRY_POINTS = (
    "plan_contraction", "plan_compiled", "simulate_amplitude",
    "open_amplitude_batch", "sample_bitstrings", "open_session",
)


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_plan_options_reach_the_planner(entry, monkeypatch):
    """Every entry point forwards every planning option: non-default
    ``repeats`` and ``slicing_mode`` arrive at the one-shot planner."""
    from repro.lowering.cache import PLAN_CACHE

    PLAN_CACHE.clear()
    calls = _spy_planners(monkeypatch)
    _entry_calls(random_1d_circuit(8, 5, seed=3))[entry](
        repeats=3, slicing_mode="peak"
    )
    assert len(calls) == 1
    assert calls[0]["repeats"] == 3
    assert calls[0]["slicing_mode"] == "peak"


@pytest.mark.parametrize(
    "bad, exc",
    [
        ({"repeat": 3}, TypeError),
        ({"slicing_mode": "depth"}, ValueError),
        ({"optimize": "greedy"}, ValueError),
        ({"backend": "pallas"}, ValueError),
    ],
)
def test_bad_plan_option_fails_before_planning(bad, exc, monkeypatch):
    """An unknown option name, or a mode no planner has, raises naming
    the offender at every entry point before any planner runs."""
    calls = _spy_planners(monkeypatch)
    (name,) = bad
    for entry, call in _entry_calls(random_1d_circuit(6, 3, seed=1)).items():
        with pytest.raises(exc, match=name):
            call(**bad)
        assert calls == [], entry
