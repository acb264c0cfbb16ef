"""Differential suite for the unified-engine refactor.

The four slice drivers (``contract_all`` / ``contract_sharded`` /
``contract_resumable`` / ``contract_multihost``) became thin strategy
adapters over :class:`repro.engine.session.ContractionSession`.  The
refactor's contract is *bitwise* identity: the jitted program bodies
moved verbatim, so the adapters must reproduce the pre-refactor outputs
exactly — not approximately — on the same plans.

Each legacy driver below is a frozen copy of the pre-refactor
implementation (taken from the last pre-engine revision), with its jit
memoization keys renamed ``legacy_*`` so it traces + compiles its OWN
program rather than sharing the adapter's (its slice ids reach
``contract_slice`` as rows of ``plan.slice_bits``, the one encoding the
plan takes) — the comparison is between
two independently compiled executables, which is what makes equality
meaningful.

Legs: {width/peak slicing} x {hoist off/on} x {fp32/bf16} on the
lowered GEMM backend, plus an einsum leg and the unsliced dense path.
The pinned circuit is the 12-qubit syc-12 family the benchmarks use,
planned at a width that forces slicing with a slice count that is NOT a
multiple of the slice batch — the ragged masked lanes are exactly where
a refactor of the padding/masking logic would diverge first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import plan_compiled
from repro.core.distributed import (
    SliceRangeCheckpoint,
    contract_resumable,
    contract_sharded,
)
from repro.core.executor import simplify_network
from repro.engine.session import ContractionSession
from repro.quantum.circuits import circuit_to_network, sycamore_like

ROWS, COLS, CYCLES, SEED = 3, 4, 8, 2
TARGET_DIM = 8
SLICE_BATCH = 3  # must not divide the slice count (ragged final batch)


@functools.lru_cache(maxsize=None)
def _leg(slicing: str, backend: str, precision: str):
    """Plan the pinned syc-12 circuit under one leg (uncached — each leg
    gets its own plan object so no jitted programs leak between legs)."""
    circuit = sycamore_like(ROWS, COLS, CYCLES, seed=SEED)
    tn, arrays = circuit_to_network(
        circuit, bitstring="0" * circuit.num_qubits
    )
    tn, arrays = simplify_network(tn, arrays)
    plan, _ = plan_compiled(
        tn, TARGET_DIM, backend=backend, precision=precision,
        slicing_mode=slicing, use_cache=False,
    )
    assert plan.num_sliced > 0  # the leg must exercise real slicing
    assert (1 << plan.num_sliced) % SLICE_BATCH != 0
    return plan, tuple(arrays)


LEGS = [
    ("width", "gemm", "fp32"),
    ("peak", "gemm", "fp32"),
    ("width", "gemm", "bf16"),
    ("peak", "gemm", "bf16"),
    ("width", "einsum", "fp32"),
]


# ----------------------------------------------------------------------
# frozen pre-refactor drivers (jit keys renamed legacy_*)
# ----------------------------------------------------------------------
def legacy_contract_all(plan, arrays, slice_batch=8, hoist=None):
    from repro.core.executor import default_hoist

    n_slices = 1 << plan.num_sliced
    if plan.num_sliced == 0:
        key = ("legacy_dense",)
        fn = plan._compiled.get(key) or plan._compiled.setdefault(
            key,
            jax.jit(lambda a: plan.contract_slice(a, plan.slice_bits(0))),
        )
        return fn(list(arrays))
    hoist = default_hoist() if hoist is None else bool(hoist)
    hoist = hoist and plan.can_hoist
    slice_batch = max(1, min(slice_batch, n_slices))
    n_batches = -(-n_slices // slice_batch)
    total = n_batches * slice_batch
    padded = total != n_slices
    key = ("legacy_all", slice_batch, hoist)
    fn = plan._compiled.get(key)
    if fn is None:
        ids = jnp.asarray(
            plan.slice_bits(np.arange(total) % n_slices)
        ).reshape(n_batches, slice_batch, plan.num_sliced)
        w = jnp.asarray(np.arange(total) < n_slices).reshape(
            n_batches, slice_batch
        )

        @jax.jit
        def run(arrs, hbufs):
            batched = jax.vmap(
                lambda sid: plan.contract_slice(
                    arrs, sid, hbufs if hoist else None
                )
            )

            def body(acc, chunk_w):
                chunk, wk = chunk_w
                contrib = batched(chunk)
                if padded:
                    contrib = jnp.where(
                        wk.reshape((-1,) + (1,) * (contrib.ndim - 1)),
                        contrib,
                        jnp.zeros((), contrib.dtype),
                    )
                return acc + jnp.sum(contrib, axis=0), None

            out_shape = jax.eval_shape(
                lambda: jnp.sum(batched(ids[0]), axis=0)
            )
            acc0 = jnp.zeros(out_shape.shape, out_shape.dtype)
            acc, _ = jax.lax.scan(body, acc0, (ids, w))
            return acc

        fn = plan._compiled.setdefault(key, run)
    hoisted = plan.contract_prologue(arrays) if hoist else []
    return fn(list(arrays), list(hoisted))


def legacy_contract_sharded(
    plan, arrays, mesh, axis_names=("data",), slice_batch=1, hoist=None
):
    from jax.sharding import PartitionSpec as P

    from repro.core.executor import default_hoist

    ndev = 1
    for ax in axis_names:
        ndev *= mesh.shape[ax]
    n_slices = 1 << plan.num_sliced
    slice_batch = max(1, min(slice_batch, n_slices))
    chunk = ndev * slice_batch
    total = -(-n_slices // chunk) * chunk
    ids = plan.slice_bits(np.arange(total) % n_slices)
    valid = np.arange(total) < n_slices

    hoist = default_hoist() if hoist is None else bool(hoist)
    hoist = hoist and plan.can_hoist
    hoisted = (
        plan.contract_prologue_replicated(arrays, mesh) if hoist else []
    )
    spec = P(axis_names)
    key = ("legacy_sharded", mesh, tuple(axis_names), slice_batch, hoist)
    fn = plan._compiled.get(key)
    if fn is None:

        @jax.jit
        def run(arrs, hbufs, ids_, valid_):
            def worker(ids_local, valid_local):
                contract = lambda sid: plan.contract_slice(  # noqa: E731
                    arrs, sid, hbufs if hoist else None
                )
                batched = jax.vmap(contract)
                idb = ids_local.reshape(-1, slice_batch, plan.num_sliced)
                vb = valid_local.reshape(-1, slice_batch)
                out_shape = jax.eval_shape(
                    lambda: contract(plan.slice_bits(0))
                )
                wshape = (-1,) + (1,) * len(out_shape.shape)

                def body(acc, iv):
                    sids, ok = iv
                    contrib = batched(sids)
                    contrib = jnp.where(
                        ok.reshape(wshape),
                        contrib,
                        jnp.zeros((), contrib.dtype),
                    )
                    return acc + jnp.sum(contrib, axis=0), None

                acc0 = jnp.zeros(out_shape.shape, out_shape.dtype)
                acc, _ = jax.lax.scan(body, acc0, (idb, vb))
                return jax.lax.psum(acc, axis_names)

            return jax.shard_map(
                worker,
                mesh=mesh,
                in_specs=(spec, spec),
                out_specs=P(),
                check_vma=False,
            )(ids_, valid_)

        fn = plan._compiled.setdefault(key, run)
    return fn(
        list(arrays), list(hoisted), jnp.asarray(ids), jnp.asarray(valid)
    )


def legacy_contract_resumable(plan, arrays, chunk=4, hoist=None):
    from repro.core.executor import default_hoist

    hoist = default_hoist() if hoist is None else bool(hoist)
    hoist = hoist and plan.can_hoist
    hoisted = plan.contract_prologue(arrays) if hoist else []
    n_slices = 1 << plan.num_sliced
    out_shape = jax.eval_shape(
        lambda: plan.contract_slice(list(arrays), plan.slice_bits(0))
    )
    state = SliceRangeCheckpoint(
        n_slices, set(), np.zeros(out_shape.shape, out_shape.dtype)
    )
    ck = ("legacy_resumable", hoist)
    contract = plan._compiled.get(ck) or plan._compiled.setdefault(
        ck,
        jax.jit(
            lambda arrs, hbufs, sid: plan.contract_slice(
                arrs, sid, hbufs if hoist else None
            )
        ),
    )
    for s, e in state.missing(chunk):
        acc = None
        for sid in range(s, e):
            r = contract(
                list(arrays), list(hoisted), jnp.asarray(plan.slice_bits(sid))
            )
            acc = r if acc is None else acc + r
        state.partial = state.partial + np.asarray(acc)
        state.add_range(s, e)
    return state.partial, state


def legacy_mh_batch(plan, arrays, sb, hoist):
    """The pre-refactor multi-host per-range program (key mh_batch):
    masked vmap over one claimed range of slice ids."""
    hoisted = plan.contract_prologue(arrays) if hoist else []
    ck = ("legacy_mh_batch", sb, hoist)
    fn = plan._compiled.get(ck)
    if fn is None:

        @jax.jit
        def fn(arrs, hbufs, ids_, valid_):
            contract = lambda sid: plan.contract_slice(  # noqa: E731
                arrs, sid, hbufs if hoist else None
            )
            contrib = jax.vmap(contract)(ids_)
            contrib = jnp.where(
                valid_.reshape((-1,) + (1,) * (contrib.ndim - 1)),
                contrib,
                jnp.zeros((), contrib.dtype),
            )
            return jnp.sum(contrib, axis=0)

        fn = plan._compiled.setdefault(ck, fn)
    return lambda ids, valid: fn(
        list(arrays), list(hoisted), jnp.asarray(plan.slice_bits(ids)),
        jnp.asarray(valid),
    )


# ----------------------------------------------------------------------
# adapter vs frozen legacy: bitwise
# ----------------------------------------------------------------------
@pytest.mark.parametrize("slicing,backend,precision", LEGS)
@pytest.mark.parametrize("hoist", [False, True])
def test_contract_all_bitwise(slicing, backend, precision, hoist):
    plan, arrays = _leg(slicing, backend, precision)
    ref = legacy_contract_all(
        plan, list(arrays), slice_batch=SLICE_BATCH, hoist=hoist
    )
    new = plan.contract_all(
        list(arrays), slice_batch=SLICE_BATCH, hoist=hoist
    )
    assert np.array_equal(np.asarray(new), np.asarray(ref))


@pytest.mark.parametrize("slicing,backend,precision", LEGS)
@pytest.mark.parametrize("hoist", [False, True])
def test_contract_sharded_bitwise(slicing, backend, precision, hoist):
    from jax.sharding import Mesh

    plan, arrays = _leg(slicing, backend, precision)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    ref = legacy_contract_sharded(
        plan, list(arrays), mesh, slice_batch=SLICE_BATCH, hoist=hoist
    )
    new = contract_sharded(
        plan, list(arrays), mesh, slice_batch=SLICE_BATCH, hoist=hoist
    )
    assert np.array_equal(np.asarray(new), np.asarray(ref))


@pytest.mark.parametrize("slicing,backend,precision", LEGS[:2] + LEGS[3:])
@pytest.mark.parametrize("hoist", [False, True])
def test_contract_resumable_bitwise(slicing, backend, precision, hoist):
    plan, arrays = _leg(slicing, backend, precision)
    ref, ref_state = legacy_contract_resumable(
        plan, list(arrays), chunk=SLICE_BATCH, hoist=hoist
    )
    new, new_state = contract_resumable(
        plan, list(arrays), chunk=SLICE_BATCH, hoist=hoist
    )
    assert np.array_equal(np.asarray(new), np.asarray(ref))
    assert new_state.done_ids() == ref_state.done_ids()


@pytest.mark.parametrize("hoist", [False, True])
def test_run_slices_matches_legacy_mh_batch(hoist):
    """The engine's run_slices primitive is bitwise the pre-refactor
    multi-host per-range program on every claimed range (including the
    final wrapped/masked one).  contract_multihost's surrounding
    scheduler/transport/claims logic is unchanged by the refactor, so
    per-range identity is driver identity."""
    plan, arrays = _leg("peak", "gemm", "fp32")
    sess = ContractionSession(plan, list(arrays), hoist=hoist)
    legacy = legacy_mh_batch(plan, list(arrays), SLICE_BATCH, sess.hoist)
    n = sess.n_slices
    for start in range(0, n, SLICE_BATCH):
        end = min(start + SLICE_BATCH, n)
        ids = np.arange(start, start + SLICE_BATCH, dtype=np.int32) % n
        valid = np.arange(start, start + SLICE_BATCH) < end
        new = sess.run_slices(ids, valid)
        ref = legacy(ids, valid)
        assert np.array_equal(np.asarray(new), np.asarray(ref))


def test_multihost_world1_matches_contract_all():
    from repro.distributed.multihost import contract_multihost

    plan, arrays = _leg("peak", "gemm", "fp32")
    res = contract_multihost(plan, list(arrays), slice_batch=SLICE_BATCH)
    assert res.complete
    ref = plan.contract_all(list(arrays), slice_batch=SLICE_BATCH)
    np.testing.assert_allclose(
        np.asarray(res.value), np.asarray(ref), rtol=1e-5, atol=1e-7
    )


def test_dense_path_bitwise():
    """Unsliced plans take the dense fast path in both eras."""
    from repro.quantum.circuits import random_1d_circuit

    circuit = random_1d_circuit(8, 4, seed=3)
    tn, arrays = circuit_to_network(circuit, bitstring="0" * 8)
    tn, arrays = simplify_network(tn, arrays)
    plan, _ = plan_compiled(tn, 30, use_cache=False)
    assert plan.num_sliced == 0
    ref = legacy_contract_all(plan, list(arrays))
    new = plan.contract_all(list(arrays))
    assert np.array_equal(np.asarray(new), np.asarray(ref))


def test_session_shares_program_across_drivers():
    """All sessions over one plan converge on ONE traced batch program
    (the _compiled memoization the serving engine relies on)."""
    plan, arrays = _leg("peak", "gemm", "fp32")
    s1 = ContractionSession(plan, list(arrays), hoist=True)
    s2 = ContractionSession(plan, list(arrays), hoist=True)
    s1.run_slices(np.arange(SLICE_BATCH, dtype=np.int32))
    fn1 = plan._compiled[("sess_batch", s1.hoist)]
    s2.run_slices(np.arange(SLICE_BATCH, dtype=np.int32))
    assert plan._compiled[("sess_batch", s2.hoist)] is fn1
