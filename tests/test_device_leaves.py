"""A session's leaves go to the device once, when it opens.

``ContractionSession`` puts its leaf arrays on the device in one
``jax.device_put`` and every strategy reads those copies, so a call
sends only its slice ids' bits and the validity mask.  The device copies
must change nothing else: each path returns bitwise what the same jitted
program returns fed the NumPy leaves, the batch program lowers to the
same text, and sessions over equal host leaves still share one hoisted
prologue (keyed by value from the host leaves, once, at open).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import plan_compiled
from repro.engine.session import ContractionSession
from repro.launch.mesh import make_host_mesh
from repro.lowering import cache as lcache
from repro.obs import metrics, trace
from repro.quantum.circuits import sycamore_like
from repro.sampling.batch import open_batch_network

# 3x4 grid, 8 cycles, two open qubits, planned at width 6: 7 sliced
# indices, 26 leaves and a slice-invariant prologue to hoist
ROWS, COLS, CYCLES, SEED, OPEN, TARGET_DIM = 3, 4, 8, 5, (10, 11), 6
IDS, VALID = [3, 64, 127, 3], [True, True, True, False]
SLICE_BATCH = 8


@functools.lru_cache(maxsize=None)
def small_plan():
    """The plan (gemm backend, peak slicing) and its host leaves."""
    circ = sycamore_like(ROWS, COLS, CYCLES, seed=SEED)
    tn, arrays = open_batch_network(circ, "0" * circ.num_qubits, OPEN)
    plan, _ = plan_compiled(
        tn, TARGET_DIM, backend="gemm", slicing_mode="peak", use_cache=False
    )
    return plan, tuple(np.asarray(a) for a in arrays)


def host_leaves() -> list:
    """Fresh NumPy copies of the leaves (equal values, new objects)."""
    return [a.copy() for a in small_plan()[1]]


def test_the_plan_slices_and_hoists():
    plan, arrays = small_plan()
    assert plan.num_sliced >= 3 and plan.can_hoist
    assert max(IDS) < 1 << plan.num_sliced
    assert all(isinstance(a, np.ndarray) for a in arrays)


@pytest.mark.parametrize("hoist", [False, True])
def test_leaves_are_device_arrays_on_the_sessions_device(hoist):
    plan, arrays = small_plan()
    sess = ContractionSession(plan, host_leaves(), hoist=hoist)
    assert len(sess.arrays) == len(arrays)
    device = jax.devices()[0]
    for a, h in zip(sess.arrays, arrays):
        assert isinstance(a, jax.Array)
        assert a.devices() == {device}
        assert a.shape == h.shape
        np.testing.assert_array_equal(np.asarray(a), h)
    # what the benchmark hands the prologue on its own
    if sess.hoist:
        for x, y in zip(
            plan.contract_prologue(sess.arrays, use_cache=False),
            sess.hoisted(),
        ):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _numpy_fed(sess, path: str, host: list):
    """The jitted program of ``path`` called with the NumPy leaves (and
    the prologue computed from them)."""
    plan, hoist = sess.plan, sess.hoist
    hbufs = plan.contract_prologue(host, use_cache=False) if hoist else []
    if path == "run_slice":
        fn = plan._compiled[("sess_slice", hoist)]
        return fn(host, hbufs, jnp.asarray(plan.slice_bits(IDS[0])))
    if path == "run_slices":
        return sess._batch_fn()(
            host, hbufs, jnp.asarray(plan.slice_bits(IDS)),
            jnp.asarray(VALID),
        )
    if path == "run_all":
        n = sess.n_slices
        ids = np.arange(n).reshape(-1, SLICE_BATCH)
        return sess._all_fn(SLICE_BATCH)(
            host, hbufs, jnp.asarray(plan.slice_bits(ids)),
            jnp.ones(ids.shape, bool),
        )
    mesh = make_host_mesh((1,), ("data",))
    fn = sess._sharded_fn(mesh, ("data",), 2)
    return fn(
        host, hbufs, jnp.asarray(plan.slice_bits(IDS)), jnp.asarray(VALID)
    )


def _device_fed(sess, path: str):
    if path == "run_slice":
        return sess.run_slice(IDS[0])
    if path == "run_slices":
        return sess.run_slices(IDS, np.asarray(VALID))
    if path == "run_all":
        return sess.run_all(SLICE_BATCH)
    mesh = make_host_mesh((1,), ("data",))
    # the ids already hold one wrapped-around lane: padded_ids keeps it
    # at four lanes, masked as VALID masks it
    return sess.run_sharded(
        mesh, ("data",), slice_batch=2, slice_ids=IDS[:3],
    )


@pytest.mark.parametrize("hoist", [False, True])
@pytest.mark.parametrize(
    "path", ["run_slice", "run_slices", "run_all", "run_sharded"]
)
def test_every_path_matches_the_numpy_fed_program_bitwise(path, hoist):
    plan, _ = small_plan()
    sess = ContractionSession(plan, host_leaves(), hoist=hoist)
    assert sess.n_slices % SLICE_BATCH == 0
    got = np.asarray(_device_fed(sess, path))
    want = np.asarray(_numpy_fed(sess, path, host_leaves()))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.abs(want).max() > 0
    np.testing.assert_array_equal(got, want)


def test_a_later_call_puts_no_leaves():
    plan, arrays = small_plan()
    prev = trace.enabled()
    trace.set_enabled(True)
    trace.reset()
    metrics.reset()
    mesh = make_host_mesh((1,), ("data",))

    def counts():
        snap = metrics.snapshot()["counters"]
        return snap["engine.leaf_puts"], snap["engine.leaf_put_bytes"]

    try:
        sess = ContractionSession(plan, host_leaves(), hoist=True)
        at_open = counts()
        for _ in range(2):
            jax.block_until_ready(sess.run_slices(IDS))
        after_calls = counts()
        jax.block_until_ready(sess.run_sharded(mesh, slice_ids=IDS))
        after_mesh = counts()
        jax.block_until_ready(sess.run_sharded(mesh, slice_ids=IDS))
        jax.block_until_ready(sess.run_slice(IDS[0]))
        after_more = counts()
        puts = [s for s in trace.get_spans() if s.name == "engine.leaves_put"]
    finally:
        trace.set_enabled(prev)
        trace.reset()
        metrics.reset()
    nbytes = sum(int(np.asarray(a, np.complex64).nbytes) for a in arrays)
    assert at_open == (len(arrays), nbytes)
    assert after_calls == at_open
    # a mesh gets its replicated copies once, on its first call
    assert after_mesh == (2 * len(arrays), 2 * nbytes)
    assert after_more == after_mesh
    assert [s.attrs["leaves"] for s in puts] == [len(arrays)] * 2


def test_sessions_over_equal_leaves_share_one_prologue(monkeypatch):
    """The prologue's cache key comes from the host leaves, by value, at
    open: a second session over equal leaves hits the first one's entry,
    and nothing on the call path keys or hashes a device array."""
    plan, _ = small_plan()
    seen = {"fingerprint_on_device": 0, "key_calls": 0, "key_on_device": 0}
    fingerprint, leaf_key = lcache.leaf_fingerprint, lcache.leaf_key

    def on_device(arrays) -> bool:
        return any(isinstance(a, jax.Array) for a in arrays)

    def counting_fingerprint(arrays, indices=None):
        seen["fingerprint_on_device"] += on_device(arrays)
        return fingerprint(arrays, indices)

    def counting_key(arrays, indices=None):
        seen["key_calls"] += 1
        seen["key_on_device"] += on_device(arrays)
        return leaf_key(arrays, indices)

    monkeypatch.setattr(lcache, "leaf_fingerprint", counting_fingerprint)
    monkeypatch.setattr(lcache, "leaf_key", counting_key)
    before = plan._hoist_cache.stats()
    s1 = ContractionSession(plan, host_leaves(), hoist=True)
    s2 = ContractionSession(plan, host_leaves(), hoist=True)
    assert seen["key_calls"] == 2  # one per session, at open
    mesh = make_host_mesh((1,), ("data",))
    h1 = s1.hoisted()
    jax.block_until_ready(s1.run_slices(IDS))
    h2 = s2.hoisted()
    jax.block_until_ready(s2.run_slices(IDS))
    jax.block_until_ready(s2.run_sharded(mesh, slice_ids=IDS))
    after = plan._hoist_cache.stats()
    assert len(h1) and all(x is y for x, y in zip(h1, h2))
    assert after["misses"] - before["misses"] <= 1  # an earlier test's
    assert after["hits"] - before["hits"] >= 1
    assert seen == {
        "fingerprint_on_device": 0, "key_calls": 2, "key_on_device": 0,
    }


@pytest.mark.parametrize("hoist", [False, True])
def test_batch_program_text_is_the_same_with_device_leaves(hoist):
    plan, _ = small_plan()
    sess = ContractionSession(plan, host_leaves(), hoist=hoist)
    hbufs = list(sess.hoisted())
    structs = (
        jax.ShapeDtypeStruct((2, plan.num_sliced), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.bool_),
    )
    fn = sess._batch_fn()
    device = fn.lower(list(sess.arrays), hbufs, *structs).as_text()
    host = fn.lower(host_leaves(), hbufs, *structs).as_text()
    assert device == host
