"""Regression tests for the PR-5 correctness fixes: chunk-agnostic
checkpoint resume, select-based (NaN-safe) ragged-batch masking, hoist
cache eviction releasing device buffers, and the sharded ragged-batch
contract."""

import gc
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import subprocess_kwargs
from repro.core import ContractionPlan, simplify_network
from repro.core.contraction_tree import ContractionTree
from repro.core.distributed import SliceRangeCheckpoint, contract_resumable
from repro.core.pathfinder import greedy_ssa_path, random_greedy_tree
from repro.core.slicing import find_slices
from repro.core.tensor_network import random_regular_tn
from repro.lowering.cache import HoistCache
from repro.quantum.circuits import circuit_to_network, random_1d_circuit


def _plan(min_sliced: int = 3):
    c = random_1d_circuit(10, 8, seed=3)
    tn, arrays = circuit_to_network(c, bitstring="0110100101")
    tn, arrays = simplify_network(tn, arrays)
    tree = random_greedy_tree(tn, repeats=4)
    S = find_slices(tree, tree.width() - min_sliced, method="lifetime")
    plan = ContractionPlan(tree, S)
    assert plan.num_sliced >= min_sliced
    return plan, arrays, tree


# ----------------------------------------------------------------------
# resume-chunk contract
# ----------------------------------------------------------------------
def test_missing_is_chunk_agnostic():
    ck = SliceRangeCheckpoint(10, set(), 0.0)
    ck.add_range(0, 4)
    assert ck.missing(4) == [(4, 8), (8, 10)]
    # a different chunk never re-enqueues completed ids
    assert ck.missing(3) == [(4, 7), (7, 10)]
    assert ck.missing(100) == [(4, 10)]
    ck.add_range(6, 8)
    # ranges stop at done islands and need not align to chunk boundaries
    assert ck.missing(4) == [(4, 6), (8, 10)]
    assert ck.done_ids() == {0, 1, 2, 3, 6, 7}


def test_legacy_range_entries_normalize():
    # checkpoints written by the old range-keyed format still resume
    ck = SliceRangeCheckpoint(8, {(0, 3), 5}, 0.0)
    assert ck.done_ids() == {0, 1, 2, 5}
    assert ck.missing(8) == [(3, 5), (6, 8)]
    ck.add_range(3, 5)
    assert ck.done_ids() == {0, 1, 2, 3, 4, 5}


def test_resume_across_chunk_sizes():
    """A checkpoint written with chunk=k1 must resume under chunk=k2
    without re-summing (double-counting) completed slices."""
    plan, arrays, tree = _plan()
    dense = np.asarray(ContractionPlan(tree, 0).contract_all(arrays))
    n_slices = 1 << plan.num_sliced
    out_shape = jax.eval_shape(
        lambda: plan.contract_slice(list(arrays), plan.slice_bits(0))
    )
    state = SliceRangeCheckpoint(
        n_slices, set(), np.zeros(out_shape.shape, out_shape.dtype)
    )
    # partial run at chunk=3, failing after two completed ranges
    with pytest.raises(RuntimeError):
        contract_resumable(plan, arrays, chunk=3, state=state, fail_on={6})
    assert state.done_ids() == set(range(6))
    # resume with a different chunk: completes, no double counting
    val, state = contract_resumable(plan, arrays, chunk=5, state=state)
    np.testing.assert_allclose(val, dense, atol=1e-4)
    assert state.done_ids() == set(range(n_slices))
    # and a third chunk size is a no-op
    val2, _ = contract_resumable(plan, arrays, chunk=7, state=state)
    np.testing.assert_allclose(val2, val, atol=1e-6)


# ----------------------------------------------------------------------
# ragged-batch masking: select, not weight-multiply
# ----------------------------------------------------------------------
def _overflow_network(seed: int = 0):
    """A closed network whose every slice contribution overflows float32
    to +inf (all-positive entries, no cancellation): the correct ragged
    sum is +inf, while a ``0 * inf`` weight-multiply mask turns it NaN."""
    tn = random_regular_tn(10, 3, seed=seed)
    rng = np.random.default_rng(seed)
    arrays = [
        (rng.uniform(0.5, 1.0, size=(2,) * len(t)) * 1e25).astype(
            np.float32
        )
        for t in tn.inputs
    ]
    tree = ContractionTree.from_ssa_path(tn, greedy_ssa_path(tn, seed=1))
    S = find_slices(tree, max(tree.width() - 2, 2), method="lifetime")
    assert S, "need at least one sliced index for a ragged batch"
    return ContractionPlan(tree, S), arrays


@pytest.mark.parametrize("hoist", [False, True])
def test_ragged_padding_does_not_leak_nan(hoist):
    plan, arrays = _overflow_network()
    n_slices = 1 << plan.num_sliced
    assert n_slices % 3 != 0  # slice_batch=3 forces a ragged final batch
    val = np.asarray(plan.contract_all(arrays, slice_batch=3, hoist=hoist))
    assert np.all(np.isinf(val)), val
    assert not np.any(np.isnan(val)), (
        "padded-lane contribution leaked through the validity mask"
    )


@pytest.mark.parametrize("hoist", [False, True])
def test_ragged_padding_correct_value(hoist):
    """Finite case: every slice_batch (ragged or not) sums identically."""
    plan, arrays, tree = _plan()
    ref = np.asarray(ContractionPlan(tree, 0).contract_all(arrays))
    for sb in (3, 5, (1 << plan.num_sliced) - 1):
        val = np.asarray(
            plan.contract_all(arrays, slice_batch=sb, hoist=hoist)
        )
        np.testing.assert_allclose(val, ref, atol=1e-4)


# ----------------------------------------------------------------------
# hoist cache: eviction releases device buffers; optional byte bound
# ----------------------------------------------------------------------
def _n_live() -> int:
    gc.collect()
    return len(jax.live_arrays())


def test_hoist_cache_eviction_releases_device_buffers():
    plan, arrays, _ = _plan()
    assert plan.can_hoist
    plan._hoist_cache = HoistCache(maxsize=2)
    n_out = len(plan.hoisted_nodes)

    def variant(k):
        return [np.asarray(a) * (1.0 + 0.01 * k) for a in arrays]

    out = plan.contract_prologue(variant(0))  # warm the jit trace
    del out
    base = _n_live()
    for k in range(1, 9):
        out = plan.contract_prologue(variant(k))
        del out
    assert len(plan._hoist_cache._entries) == 2
    grown = _n_live() - base
    # 8 inserts at maxsize=2: evictions must have dropped the buffer
    # refs, so growth is bounded by ~2 entries, not 8
    assert grown <= 2 * n_out + 4, (grown, n_out)
    plan._hoist_cache.clear()
    assert _n_live() <= base + 4
    assert plan._hoist_cache.total_bytes == 0


def test_hoist_cache_byte_bound():
    plan, arrays, _ = _plan()
    assert plan.can_hoist
    outs = plan.contract_prologue(arrays, use_cache=False)
    entry_bytes = sum(int(o.nbytes) for o in outs)
    del outs
    # bound admits ~2 entries; entry count alone would admit 8
    plan._hoist_cache = HoistCache(maxsize=8, max_bytes=2 * entry_bytes)
    for k in range(6):
        out = plan.contract_prologue(
            [np.asarray(a) * (1.0 + 0.01 * k) for a in arrays]
        )
        del out
    cache = plan._hoist_cache
    assert len(cache._entries) <= 2
    assert cache.total_bytes <= 2 * entry_bytes
    assert cache.total_bytes == sum(cache._entry_bytes.values())
    # an oversized single entry is still admitted (best-effort bound)
    cache.max_bytes = 1
    out = plan.contract_prologue(
        [np.asarray(a) * 1.5 for a in arrays]
    )
    del out
    assert len(cache._entries) == 1


# ----------------------------------------------------------------------
# sharded ragged batches (shard_map, 8 virtual devices)
# ----------------------------------------------------------------------
SHARDED_RAGGED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.quantum.circuits import random_1d_circuit, circuit_to_network
from repro.core import simplify_network, ContractionPlan
from repro.core.pathfinder import random_greedy_tree
from repro.core.slicing import find_slices
from repro.core.distributed import contract_sharded
from repro.launch.mesh import make_host_mesh

c = random_1d_circuit(10, 8, seed=3)
tn, arrays = circuit_to_network(c, bitstring="0110100101")
tn, arrays = simplify_network(tn, arrays)
tree = random_greedy_tree(tn, repeats=4)
S = find_slices(tree, 4, method="lifetime")
plan = ContractionPlan(tree, S)
assert (1 << plan.num_sliced) % (8 * 3) != 0  # genuinely ragged
dense = ContractionPlan(tree, 0).contract_all(arrays)
mesh = make_host_mesh((8,), ("data",))
# slice_batch=3 over 8 devices: per-device ids stay tileable only via
# the executor's padding contract (no divisibility assumption)
v = contract_sharded(plan, arrays, mesh, slice_batch=3)
assert np.allclose(np.asarray(v), np.asarray(dense), atol=1e-4)
# a slice_batch larger than the per-device share still works
v2 = contract_sharded(plan, arrays, mesh, slice_batch=7)
assert np.allclose(np.asarray(v2), np.asarray(dense), atol=1e-4)
print("DONE")
"""


def test_contract_sharded_ragged_batches():
    r = subprocess.run(
        [sys.executable, "-c", SHARDED_RAGGED],
        capture_output=True, text=True, timeout=900,
        **subprocess_kwargs(),
    )
    assert "DONE" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
