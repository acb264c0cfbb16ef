"""Lifetime-based memory planning + the lane-dense GEMM step.

Covers: linear-scan live-set peaks vs a brute-force executor simulation
on random trees (naive and prologue/epilogue segments), slot-assignment
validity, lane-dense step equivalence with the einsum oracle and
*bitwise* agreement with the permute + ``tiled_matmul`` reference at
matched tile blocking (complex Karatsuba included), refiner selection
of the Pallas kernel for MXU-sized steps, the peak-aware slicer contract
(|S_peak| <= |S_width|, explicit byte budgets honored), the
device-identity prologue cache key, hoisted-buffer donation, and the
pinned syc-12 peak-bytes regression gate."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import random_closed_network, random_tree
from repro.core import ContractionPlan, simplify_network, simulate_amplitude
from repro.core.executor import pair_contract_inds
from repro.core.lifetime import step_lifetimes
from repro.core.pathfinder import random_greedy_tree
from repro.core.slicing import (
    find_slices,
    peak_budget_for_width,
    refine_slices_for_peak,
)
from repro.core.tensor_network import popcount
from repro.lowering import gemm_form, lower_step, refine_schedule, refine_step
from repro.lowering.cache import leaf_key
from repro.lowering.memory import node_nbytes, peak_bytes, plan_memory
from repro.lowering.partition import partition_tree
from repro.lowering.layout import dense_step
from repro.lowering.refiner import GemmSpec
from repro.kernels import ops
from repro.quantum.circuits import circuit_to_network, random_1d_circuit

RNG = np.random.default_rng(0)
ITEMSIZE = 8  # complex64


# ----------------------------------------------------------------------
# brute-force oracle: replay the executor's env discipline and record the
# max over live sets (independent of the planner's event sweep)
# ----------------------------------------------------------------------
def _simulate_segment_peak(tree, smask, entry, steps, pinned=()):
    """Max live bytes over an executor replay: all entry buffers resident
    up front, each step's output allocated while both inputs are still
    live, non-pinned inputs dropped after their (single) consumption."""
    live = {v: node_nbytes(tree, v, smask, ITEMSIZE) for v in entry}
    peak = sum(live.values())
    pinned = set(pinned)
    for lhs, rhs, out in steps:
        live[out] = node_nbytes(tree, out, smask, ITEMSIZE)
        peak = max(peak, sum(live.values()))
        for u in (lhs, rhs):
            if u not in pinned:
                del live[u]
    return peak


def _random_smask(tree, rng, max_bits=4):
    closed = [
        b
        for b in range(tree.tn.num_inds)
        if not (tree.tn.open_mask >> b) & 1
    ]
    k = int(rng.integers(1, max_bits + 1))
    chosen = rng.choice(closed, size=min(k, len(closed)), replace=False)
    m = 0
    for b in chosen:
        m |= 1 << int(b)
    return m


def _check_plan_against_bruteforce(tree, smask):
    mem = plan_memory(tree, smask, itemsize=ITEMSIZE)
    order = tree.contract_order()
    steps = [(*tree.children[v], v) for v in order]
    want = _simulate_segment_peak(
        tree, smask, range(tree.tn.num_tensors), steps
    )
    assert mem.naive.peak_bytes == want
    if mem.prologue is not None:
        part = partition_tree(tree, smask)
        pro = [(*tree.children[v], v) for v in part.invariant_nodes]
        assert mem.prologue.peak_bytes == _simulate_segment_peak(
            tree, smask, part.prologue_leaves, pro
        )
    if mem.epilogue is not None:
        part = partition_tree(tree, smask)
        epi = [(*tree.children[v], v) for v in part.epilogue_nodes]
        assert mem.epilogue.peak_bytes == _simulate_segment_peak(
            tree, smask,
            part.epilogue_leaves + part.hoisted_nodes, epi,
            pinned=part.hoisted_nodes,
        )
    return mem


def test_peak_matches_bruteforce_fixed():
    for seed in range(8):
        tn = random_closed_network(6 + seed, 3, seed)
        tree = random_tree(tn, seed=seed)
        rng = np.random.default_rng(seed)
        _check_plan_against_bruteforce(tree, 0)
        _check_plan_against_bruteforce(tree, _random_smask(tree, rng))


@given(n=st.integers(6, 20), seed=st.integers(0, 10_000))
@settings(max_examples=25)
def test_peak_matches_bruteforce_property(n, seed):
    """Linear-scan peak == brute-force max over live sets on random
    trees, all three segments, random slicing masks."""
    tn = random_closed_network(n, 3, seed)
    tree = random_tree(tn, seed=seed)
    rng = np.random.default_rng(seed)
    _check_plan_against_bruteforce(tree, _random_smask(tree, rng))


@given(n=st.integers(6, 20), seed=st.integers(0, 10_000))
@settings(max_examples=25)
def test_certified_peak_matches_full_plan(n, seed):
    """The allocator-free fast path the slicer/co-optimizer score with
    agrees exactly with the full MemoryPlan's certified peak."""
    from repro.lowering.memory import certified_peak

    tn = random_closed_network(n, 3, seed)
    tree = random_tree(tn, seed=seed)
    rng = np.random.default_rng(seed)
    for smask in (0, _random_smask(tree, rng)):
        mem = plan_memory(tree, smask, itemsize=8)
        assert certified_peak(tree, smask, 8) == max(
            mem.peak_bytes, mem.peak_bytes_hoisted
        )


def test_slot_assignment_valid():
    """Buffers sharing a slot have disjoint closed lifetimes, every
    buffer fits its slot, and the slot total bounds the true peak."""
    for seed in range(6):
        tn = random_closed_network(10 + seed, 3, seed)
        tree = random_tree(tn, seed=seed)
        rng = np.random.default_rng(seed)
        smask = _random_smask(tree, rng)
        mem = plan_memory(tree, smask, itemsize=ITEMSIZE)
        for seg in (mem.naive, mem.prologue, mem.epilogue):
            if seg is None:
                continue
            birth, death = step_lifetimes(
                list(seg.steps), seg.entry, seg.outputs
            )
            by_slot: dict = {}
            for v, sid in seg.slot_of.items():
                assert seg.nbytes[v] <= seg.slot_bytes[sid]
                by_slot.setdefault(sid, []).append(v)
            for members in by_slot.values():
                ivals = sorted((birth[v], death[v]) for v in members)
                for (b0, d0), (b1, d1) in zip(ivals, ivals[1:]):
                    assert d0 < b1, (seg.name, ivals)
            assert seg.slot_total_bytes() >= seg.peak_bytes
            # pinned buffers are never slot-assigned or freed
            for v in seg.pinned:
                assert v not in seg.slot_of
                for dead in seg.frees.values():
                    assert v not in dead


def test_frees_cover_every_intermediate_once():
    tn = random_closed_network(12, 3, 3)
    tree = random_tree(tn, seed=3)
    mem = plan_memory(tree, 0, itemsize=ITEMSIZE)
    seg = mem.naive
    freed = [u for dead in seg.frees.values() for u in dead]
    assert len(freed) == len(set(freed))
    # everything except the root dies exactly once
    assert set(freed) == set(tree.emask) - {tree.root}


def test_epilogue_peak_scales_with_slice_batch():
    c = random_1d_circuit(10, 8, seed=3)
    tn, arrays = circuit_to_network(c, bitstring="0110100101")
    tn, arrays = simplify_network(tn, arrays)
    tree = random_greedy_tree(tn, repeats=4)
    S = find_slices(tree, 4, method="lifetime")
    mem = plan_memory(tree, S, itemsize=ITEMSIZE)
    p1, p4 = mem.epilogue_peak(1), mem.epilogue_peak(4)
    pinned = mem.epilogue.pinned_bytes
    assert p1 == mem.epilogue.peak_bytes
    assert p4 == pinned + 4 * (p1 - pinned)


# ----------------------------------------------------------------------
# fused transpose-GEMM
# ----------------------------------------------------------------------
def _random_form(rng, nb, nm, nn, nk, sizes_from=(1, 6)):
    batch = [f"b{i}" for i in range(nb)]
    ms = [f"m{i}" for i in range(nm)]
    ns = [f"n{i}" for i in range(nn)]
    ks = [f"k{i}" for i in range(nk)]
    sizes = {
        ix: int(rng.integers(*sizes_from)) for ix in batch + ms + ns + ks
    }
    inds_a = batch + ms + ks
    inds_b = batch + ks + ns
    rng.shuffle(inds_a)
    rng.shuffle(inds_b)
    _, inds_out = pair_contract_inds(
        tuple(inds_a), tuple(inds_b), frozenset(batch)
    )
    form = lower_step(inds_a, inds_b, inds_out, sizes.__getitem__)
    sa = tuple(sizes[ix] for ix in inds_a)
    sb = tuple(sizes[ix] for ix in inds_b)
    return form, sa, sb


def _dense_vs_einsum(seed, nb, nm, nn, nk, complex_, sizes_from=(1, 6)):
    rng = np.random.default_rng(seed)
    form, sa, sb = _random_form(rng, nb, nm, nn, nk, sizes_from)
    dtype = np.complex64 if complex_ else np.float32
    a = rng.normal(size=sa)
    b = rng.normal(size=sb)
    if complex_:
        a = a + 1j * rng.normal(size=sa)
        b = b + 1j * rng.normal(size=sb)
    a, b = a.astype(dtype), b.astype(dtype)
    spec = GemmSpec(form, "pallas", 4, 4, 4, 0.0, 0.0)
    got = np.asarray(gemm_form.apply(spec, jnp.asarray(a), jnp.asarray(b)))
    want = np.einsum(form.expr, a, b)
    scale = max(1.0, np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * scale)


@pytest.mark.parametrize("complex_", [False, True])
@pytest.mark.parametrize(
    "nb,nm,nn,nk",
    [(0, 1, 1, 1), (1, 2, 2, 2), (2, 1, 2, 0), (0, 2, 1, 2), (1, 0, 2, 1),
     (0, 0, 0, 2)],
)
def test_dense_step_matches_einsum_fixed(nb, nm, nn, nk, complex_):
    for seed in (0, 1):
        _dense_vs_einsum(seed, nb, nm, nn, nk, complex_)


@given(
    seed=st.integers(0, 10_000),
    nb=st.integers(0, 2),
    nm=st.integers(0, 2),
    nn=st.integers(0, 2),
    nk=st.integers(0, 2),
    complex_=st.booleans(),
)
@settings(max_examples=30)
def test_dense_step_property(seed, nb, nm, nn, nk, complex_):
    """Random pairwise contractions (random role counts, sizes 1..5,
    shuffled axis orders, complex Karatsuba + real) — the lane-dense
    Pallas step == einsum."""
    _dense_vs_einsum(seed, nb, nm, nn, nk, complex_)


@pytest.mark.parametrize("complex_", [False, True])
@pytest.mark.parametrize(
    "nb,nm,nn,nk,tile",
    [(0, 3, 3, 3, 4), (1, 2, 2, 2, 4), (0, 4, 3, 4, 8), (2, 2, 2, 3, 2)],
)
def test_dense_step_bitwise_vs_tiled_matmul(nb, nm, nn, nk, tile, complex_):
    """Bit-agreement with a plain numpy permute + ``tiled_matmul`` at
    matched tile blocking: the lane-dense route only moves data, so the
    kernel sees the same tile values in the same K order and the results
    must be *bitwise* identical (complex via the same Karatsuba on both
    sides)."""
    rng = np.random.default_rng(7 * nb + nm + nn + nk + tile)
    form, sa, sb = _random_form(rng, nb, nm, nn, nk, sizes_from=(2, 3))
    dtype = np.complex64 if complex_ else np.float32
    a = rng.normal(size=sa)
    b = rng.normal(size=sb)
    if complex_:
        a = a + 1j * rng.normal(size=sa)
        b = b + 1j * rng.normal(size=sb)
    a, b = a.astype(dtype), b.astype(dtype)
    spec = GemmSpec(form, "pallas", tile, tile, tile, 0.0, 0.0)
    size = dict(zip(form.inds_a, sa))
    size.update(zip(form.inds_b, sb))
    ds = dense_step(
        form.inds_a, form.inds_b, form.inds_out, size.__getitem__,
        canonical=True,
    )
    got = np.asarray(
        gemm_form.contract_flat(
            spec, ds, jnp.asarray(a).reshape(-1), jnp.asarray(b).reshape(-1)
        )
    )
    a2 = a.transpose([form.inds_a.index(i) for i in ds.a_gemm])
    b2 = b.transpose([form.inds_b.index(i) for i in ds.b_gemm])
    a2 = jnp.asarray(a2.reshape(ds.a_shape))
    b2 = jnp.asarray(b2.reshape(ds.b_shape))
    ref = np.stack([
        np.asarray(
            ops.matmul(
                a2[i], b2[i], bm=tile, bn=tile, bk=tile,
                min_kernel_dim=1, interpret=True,
            )
        )
        for i in range(form.B)
    ]).reshape(-1)
    assert got.dtype == ref.dtype
    assert np.array_equal(got, ref), (form.expr, tile)


def test_dense_step_under_vmap():
    """The Pallas step must run inside the executor's slice-batch vmap."""
    rng = np.random.default_rng(3)
    form, sa, sb = _random_form(rng, 1, 2, 2, 2, sizes_from=(2, 3))
    a = rng.normal(size=sa).astype(np.float32)
    b = rng.normal(size=sb).astype(np.float32)
    spec = GemmSpec(form, "pallas", 4, 4, 4, 0.0, 0.0)
    va = jnp.stack([jnp.asarray(a), 2.0 * jnp.asarray(a)])
    vb = jnp.stack([jnp.asarray(b), jnp.asarray(b)])
    got = jax.vmap(lambda x, y: gemm_form.apply(spec, x, y))(va, vb)
    want = np.einsum(form.expr, a, b)
    np.testing.assert_allclose(
        np.asarray(got[1]), 2.0 * want, rtol=0,
        atol=1e-4 * max(1.0, np.abs(want).max()),
    )


def test_pallas_spec_adapts_to_64bit_arrays():
    """A Pallas spec handed complex128 arrays at trace time must route to
    the full-precision dot, not truncate through the fp32 kernel."""
    jax.config.update("jax_enable_x64", True)
    try:
        rng = np.random.default_rng(5)
        form, sa, sb = _random_form(rng, 0, 2, 2, 2, sizes_from=(2, 3))
        a = (rng.normal(size=sa) + 1j * rng.normal(size=sa)).astype(
            np.complex128
        )
        b = (rng.normal(size=sb) + 1j * rng.normal(size=sb)).astype(
            np.complex128
        )
        spec = GemmSpec(form, "pallas", 4, 4, 4, 0.0, 0.0)
        got = np.asarray(
            gemm_form.apply(spec, jnp.asarray(a), jnp.asarray(b))
        )
        assert got.dtype == np.complex128
        want = np.einsum(form.expr, a, b)
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-10 * max(1.0, np.abs(want).max())
        )
    finally:
        jax.config.update("jax_enable_x64", False)


def _big_pow2_form(rng):
    """An MXU-sized all-power-of-two form."""
    ms = [f"m{i}" for i in range(8)]
    ns = [f"n{i}" for i in range(8)]
    ks = [f"k{i}" for i in range(8)]
    sizes = {ix: 2 for ix in ms + ns + ks}
    inds_a = ms + ks
    inds_b = ks + ns
    rng.shuffle(inds_a)
    rng.shuffle(inds_b)
    _, inds_out = pair_contract_inds(
        tuple(inds_a), tuple(inds_b), frozenset()
    )
    return lower_step(inds_a, inds_b, inds_out, sizes.__getitem__)


def test_refiner_picks_pallas_for_mxu_sized_steps():
    form = _big_pow2_form(np.random.default_rng(0))
    spec = refine_step(form, np.complex64)
    assert spec.backend == "pallas"
    assert spec.transpose_bytes > 0.0
    assert form.M % spec.bm == 0
    assert form.N % spec.bn == 0
    assert form.K % spec.bk == 0
    sched = refine_schedule(
        [(form.inds_a, form.inds_b, form.inds_out)],
        {**{ix: 2 for ix in form.inds_a}, **{ix: 2 for ix in form.inds_b}}
        .__getitem__,
        dtype=np.complex64,
    )
    assert sched.backend_counts() == {"pallas": 1}
    assert "pallas=1" in sched.summary_row()


def test_refiner_keeps_sub_tile_steps_off_pallas():
    """A step with a sub-MXU dimension stays on XLA's dot."""
    ms = [f"m{i}" for i in range(8)]
    ks = [f"k{i}" for i in range(8)]
    sizes = {ix: 2 for ix in ms + ks + ["n0"]}
    form = lower_step(
        tuple(ms + ks), tuple(ks + ["n0"]), tuple(ms + ["n0"]),
        sizes.__getitem__,
    )
    assert refine_step(form, np.complex64).backend == "dot"


# ----------------------------------------------------------------------
# peak-aware slicing
# ----------------------------------------------------------------------
def _certified_peak(tree, S):
    mem = plan_memory(tree, S, itemsize=ITEMSIZE)
    return max(mem.peak_bytes, mem.peak_bytes_hoisted)


def test_peak_mode_never_larger_than_width_mode():
    """|S_peak| <= |S_width| on every instance, and the refined mask
    still honors the width-mode budget max(live-factor bound, achieved
    width certified peak) — certified over both the naive and the
    hoisted (prologue/epilogue, pinned frontier) execution modes."""
    strict = 0
    for seed in range(6):
        c = random_1d_circuit(10 + (seed % 3), 8, seed=seed)
        tn, arrays = circuit_to_network(c, bitstring="0" * c.num_qubits)
        tn, arrays = simplify_network(tn, arrays)
        tree = random_tree(tn, seed=seed)
        target = max(tree.width() - 3, 4)
        Sw = find_slices(tree, target, method="lifetime")
        Sp = find_slices(tree, target, method="lifetime", mode="peak")
        assert popcount(Sp) <= popcount(Sw)
        budget = max(
            peak_budget_for_width(target), _certified_peak(tree, Sw)
        )
        assert _certified_peak(tree, Sp) <= budget
        if popcount(Sp) < popcount(Sw):
            strict += 1
    assert strict > 0  # the pool must exhibit a strict improvement


def test_peak_mode_results_agree():
    """Peak-mode slicing changes |S| only — the contraction value must
    not move."""
    c = random_1d_circuit(10, 8, seed=3)
    tn, arrays = circuit_to_network(c, bitstring="0110100101")
    tn, arrays = simplify_network(tn, arrays)
    tree = random_greedy_tree(tn, repeats=4)
    dense = np.asarray(ContractionPlan(tree, 0).contract_all(arrays))
    Sp = find_slices(tree, 4, method="lifetime", mode="peak")
    got = np.asarray(
        ContractionPlan(tree, Sp).contract_all(arrays, slice_batch=4)
    )
    np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-5)


def test_explicit_budget_tops_up():
    """A hard explicit byte budget tighter than the width result's peak
    forces deeper slicing until the certified peak fits."""
    tn = random_closed_network(14, 3, 2)
    tree = random_tree(tn, seed=2)
    target = max(tree.width() - 2, 3)
    S = find_slices(tree, target, method="lifetime")
    budget = _certified_peak(tree, S) // 2
    S2 = refine_slices_for_peak(tree, S, target, budget_bytes=budget)
    assert _certified_peak(tree, S2) <= budget


def test_peak_monotone_in_smask():
    """Adding a sliced index never increases the planned peak — the
    property the prune/top-up loops rely on."""
    tn = random_closed_network(12, 3, 5)
    tree = random_tree(tn, seed=5)
    rng = np.random.default_rng(5)
    S = _random_smask(tree, rng, max_bits=3)
    for b in range(tree.tn.num_inds):
        if (S >> b) & 1 or (tree.tn.open_mask >> b) & 1:
            continue
        assert peak_bytes(tree, S | (1 << b)) <= peak_bytes(tree, S)


# ----------------------------------------------------------------------
# executor + report integration
# ----------------------------------------------------------------------
def test_report_memory_fields():
    c = random_1d_circuit(9, 7, seed=11)
    res = simulate_amplitude(c, "011010010", target_dim=4, use_cache=False)
    rep = res.report
    assert rep.peak_bytes > 0
    assert rep.peak_bytes_hoisted > 0
    assert rep.buffer_slots > 0
    assert "peak=" in rep.row() and "slots=" in rep.row()
    mem = res.plan.memory_plan()
    assert mem.peak_bytes == rep.peak_bytes
    # the slot plan never needs more buffers than a no-reuse executor
    assert mem.buffer_slots <= len(mem.naive.nbytes)


def test_hoist_cache_device_identity_key():
    """Device-resident leaves are keyed by buffer identity — no value
    hashing/host transfer; host leaves still key by value."""
    host = [np.ones((2, 2), np.complex64), np.zeros(2, np.complex64)]
    k1, keep1 = leaf_key(host)
    k2, _ = leaf_key([a.copy() for a in host])
    assert k1 == k2  # host arrays: equal values -> equal keys
    assert keep1 == ()  # nothing to pin
    dev = [jnp.asarray(a) for a in host]
    dk1, dkeep = leaf_key(dev)
    dk2, _ = leaf_key(dev)
    assert dk1 == dk2  # same buffers -> same key
    assert len(dkeep) == 2 and dkeep[0] is dev[0]  # ids pinned alive
    dk3, _ = leaf_key([jnp.asarray(a) for a in host])
    assert dk3 != dk1  # distinct device buffers miss (safe direction)
    assert dk1 != k1  # identity keys never collide with value keys


def test_prologue_cache_hits_on_device_arrays():
    """Passing the same device arrays twice must hit the hoist cache
    without hashing their values."""
    c = random_1d_circuit(10, 8, seed=5)
    tn, arrays = circuit_to_network(c, bitstring="0" * 10)
    tn, arrays = simplify_network(tn, arrays)
    tree = random_greedy_tree(tn, repeats=4)
    S = find_slices(tree, 4, method="lifetime")
    plan = ContractionPlan(tree, S)
    assert plan.can_hoist
    dev = [jnp.asarray(a) for a in arrays]
    h1 = plan.contract_prologue(dev)
    assert plan._hoist_cache.stats()["misses"] == 1
    h2 = plan.contract_prologue(dev)
    assert plan._hoist_cache.stats()["hits"] == 1
    for x, y in zip(h1, h2):
        assert x is y
    # a distinct device copy misses (identity key) but stays correct
    dev2 = [jnp.asarray(a) for a in arrays]
    h3 = plan.contract_prologue(dev2)
    assert plan._hoist_cache.stats()["misses"] == 2
    for x, y in zip(h1, h3):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-7)


def test_hoist_cache_disabled_still_exact(monkeypatch):
    """With the hoist cache disabled (no key, no entry) the two-phase
    path re-materializes the prologue per call and stays exact."""
    monkeypatch.setenv("REPRO_HOIST_CACHE_SIZE", "0")
    c = random_1d_circuit(10, 8, seed=3)
    tn, arrays = circuit_to_network(c, bitstring="0110100101")
    tn, arrays = simplify_network(tn, arrays)
    tree = random_greedy_tree(tn, repeats=4)
    S = find_slices(tree, 4, method="lifetime")
    dense = np.asarray(ContractionPlan(tree, 0).contract_all(arrays))
    plan = ContractionPlan(tree, S)
    assert plan.can_hoist and plan._hoist_cache.maxsize == 0
    got = np.asarray(plan.contract_all(arrays, slice_batch=4, hoist=True))
    np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-5)
    assert len(plan._hoist_cache) == 0  # nothing was cached


# ----------------------------------------------------------------------
# pinned regression gate (CI: peak on the syc-12 plan must not grow)
# ----------------------------------------------------------------------
def test_syc12_peak_regression():
    from repro.quantum.circuits import sycamore_like

    here = os.path.dirname(os.path.abspath(__file__))
    with open(
        os.path.join(here, "..", "experiments", "memory", "pinned_syc12.json")
    ) as f:
        pinned = json.load(f)
    circ = sycamore_like(4, 5, 12, seed=0)
    tn, arrays = circuit_to_network(circ, bitstring="0" * circ.num_qubits)
    tn, arrays = simplify_network(tn, arrays)
    tree = random_greedy_tree(
        tn, repeats=pinned["planner_repeats"], seed=pinned["planner_seed"]
    )
    target = max(tree.width() - 4, 8)
    assert target == pinned["target_dim"]
    S = find_slices(tree, target, method="lifetime")
    mem = plan_memory(tree, S, itemsize=pinned["itemsize"])
    assert mem.peak_bytes <= pinned["peak_bytes"]
    assert mem.peak_bytes_hoisted <= pinned["peak_bytes_hoisted"]

