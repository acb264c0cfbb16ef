import os
import random

import pytest
from hypothesis import HealthCheck, settings

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def subprocess_kwargs() -> dict:
    """cwd/env for tests that re-exec python with a multi-device XLA_FLAGS
    (portable across checkouts — CI does not live at /root/repo)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    return {"env": env, "cwd": REPO_ROOT}


settings.register_profile(
    "ci",
    max_examples=25,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)
settings.load_profile("ci")


def random_closed_network(n_tensors: int, degree: int, seed: int):
    from repro.core.tensor_network import random_regular_tn

    return random_regular_tn(n_tensors, degree, seed=seed)


def random_tree(tn, seed: int = 0):
    from repro.core.contraction_tree import ContractionTree
    from repro.core.pathfinder import greedy_ssa_path

    path = greedy_ssa_path(tn, seed=seed, temperature=0.5 if seed % 2 else 0.0)
    return ContractionTree.from_ssa_path(tn, path)


@pytest.fixture
def small_circuit():
    from repro.quantum.circuits import random_1d_circuit

    return random_1d_circuit(8, 6, seed=7)
