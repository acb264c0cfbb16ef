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


def greedy_layout(plan):
    """``plan``'s steps each choosing its store order alone, with no
    consumer's hint (``lowering.layout.dense_step``): the dense steps,
    every node's order, and their route bytes (:func:`layout_bytes`)."""
    from repro.lowering.layout import dense_step

    order = {i: plan.store_order[i] for i in range(plan.tn.num_tensors)}
    dense = []
    specs = plan.schedule.specs if plan.schedule else [None] * len(
        plan.steps)
    for st, spec in zip(plan.steps, specs):
        ds = dense_step(
            order[st.lhs], order[st.rhs], st.inds_out, plan.tn.size_of,
            canonical=spec is not None and spec.backend == "pallas",
        )
        dense.append(ds)
        order[st.out] = ds.out_order
    return dense, order, layout_bytes(plan, dense, order)


def layout_bytes(plan, dense, order) -> int:
    """Route bytes of every step's operand permutes and the root's
    permute into ``plan.out_inds``, counted from the orders given."""
    from repro.lowering.layout import route_bytes, step_route_bytes

    size = plan.dtype.itemsize
    return sum(step_route_bytes(d, size) for d in dense) + route_bytes(
        order[plan.root], plan.out_inds, plan.tn.size_of, size)


@pytest.fixture
def small_circuit():
    from repro.quantum.circuits import random_1d_circuit

    return random_1d_circuit(8, 6, seed=7)
