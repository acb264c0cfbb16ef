"""Lane-dense storage: permutation routes and per-step GEMM layouts.

Checks that every route of :func:`repro.lowering.layout.transpose_orders`
ends at the requested order, moves data exactly like a numpy transpose,
and keeps a minor group of the promised width on both sides of each of
its transposes; that :func:`repro.lowering.layout.dense_step` plus
:func:`repro.lowering.gemm_form.contract_flat` contract exactly like
``einsum`` in every orientation it picks, with and without a consumer's
hint; and that :func:`repro.lowering.layout.store_layout` never models
more route bytes than each step choosing for itself, reports what it
chose, and leaves every result as it was.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (
    greedy_layout,
    layout_bytes,
    random_closed_network,
    random_tree,
)
from repro.core.api import plan_compiled
from repro.core.executor import (
    ContractionPlan,
    einsum_expr,
    pair_contract_inds,
    simplify_network,
)
from repro.engine.session import ContractionSession
from repro.lowering import gemm_form
from repro.lowering.layout import (
    LANE,
    _dense_move,
    _lane,
    dense_step,
    permute_flat,
    transpose_orders,
)
from repro.obs import metrics, trace
from repro.quantum.circuits import circuit_to_network, sycamore_like
from repro.sampling.batch import open_batch_network


def _two(_):
    return 2


@pytest.mark.parametrize("rank", [3, 9, 12, 14, 15, 17, 19, 21, 23])
@pytest.mark.parametrize("seed", range(4))
def test_route_reaches_target_densely(rank, seed):
    rng = np.random.default_rng(100 * rank + seed)
    src = tuple(range(rank))
    dst = tuple(int(i) for i in rng.permutation(rank))
    route = transpose_orders(src, dst, _two)
    if src == dst:
        assert route == []
        return
    assert route[-1] == dst
    assert len(route) <= 5
    if 2 ** rank >= 1 << 10:
        lane = _lane(src, _two)
        cur = src
        for nxt in route:
            assert _dense_move(cur, nxt, _two, lane), (cur, nxt)
            cur = nxt
    if rank >= 21:
        assert _lane(src, _two) == LANE


@pytest.mark.parametrize("rank", [4, 11, 14, 16])
@pytest.mark.parametrize("seed", range(3))
def test_permute_flat_matches_numpy(rank, seed):
    rng = np.random.default_rng(seed)
    src = tuple(f"i{j}" for j in rng.permutation(rank))
    dst = tuple(f"i{j}" for j in rng.permutation(rank))
    x = rng.standard_normal(2 ** rank).astype(np.float32)
    got = np.asarray(permute_flat(jnp.asarray(x), src, dst, _two))
    want = x.reshape((2,) * rank).transpose(
        [src.index(a) for a in dst]
    ).reshape(-1)
    assert np.array_equal(got, want)


def test_permute_flat_mixed_sizes():
    sizes = {"a": 3, "b": 5, "c": 2, "d": 7}
    src, dst = ("a", "b", "c", "d"), ("d", "b", "a", "c")
    x = np.arange(3 * 5 * 2 * 7, dtype=np.float32)
    got = np.asarray(permute_flat(jnp.asarray(x), src, dst, sizes.get))
    want = x.reshape(3, 5, 2, 7).transpose(3, 1, 0, 2).reshape(-1)
    assert np.array_equal(got, want)


def _random_step(rng, nb, nm, nn, nk):
    labels = iter(f"x{j}" for j in range(64))
    batch = [next(labels) for _ in range(nb)]
    m = [next(labels) for _ in range(nm)]
    n = [next(labels) for _ in range(nn)]
    k = [next(labels) for _ in range(nk)]
    a = list(rng.permutation(batch + m + k))
    b = list(rng.permutation(batch + k + n))
    _, out = pair_contract_inds(tuple(a), tuple(b), frozenset(batch))
    return tuple(a), tuple(b), out


@pytest.mark.parametrize(
    "nb,nm,nn,nk",
    [(0, 1, 1, 1), (0, 8, 2, 2), (0, 2, 8, 2), (0, 2, 2, 8), (1, 6, 1, 3),
     (2, 3, 3, 0), (0, 0, 0, 4), (0, 9, 0, 3)],
)
@pytest.mark.parametrize("complex_", [False, True])
@pytest.mark.parametrize("hint", [False, True])
def test_dense_step_matches_einsum(nb, nm, nn, nk, complex_, hint):
    rng = np.random.default_rng(nb + 3 * nm + 7 * nn + 11 * nk)
    ia, ib, io = _random_step(rng, nb, nm, nn, nk)
    # a consumer contracting every other kept index, in a shuffled order
    kc = tuple(rng.permutation(io))[::2] if hint else None
    ds = dense_step(ia, ib, io, _two, consumer_k=kc)
    a = rng.standard_normal((2,) * len(ia))
    b = rng.standard_normal((2,) * len(ib))
    if complex_:
        a = a + 1j * rng.standard_normal(a.shape)
        b = b + 1j * rng.standard_normal(b.shape)
        a, b = a.astype(np.complex64), b.astype(np.complex64)
    else:
        a, b = a.astype(np.float32), b.astype(np.float32)
    got = np.asarray(gemm_form.contract_flat(
        None, ds, jnp.asarray(a).reshape(-1), jnp.asarray(b).reshape(-1)
    ))
    want = np.einsum(einsum_expr(ia, ib, io), a, b)
    want = want.transpose([io.index(i) for i in ds.out_order]).reshape(-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dense_step_puts_larger_group_minor():
    """Each operand's minor group is its larger free group, and the
    output's is the larger of M and N."""
    rng = np.random.default_rng(0)
    ia, ib, io = _random_step(rng, 0, 10, 2, 3)  # M = 2^10 > N = 4
    ds = dense_step(ia, ib, io, _two)
    assert ds.a_shape == (1, 8, 1024)  # (B, K, M): M minor
    assert ds.b_shape == (1, 4, 8)  # (B, N, K): K minor
    assert not ds.swap and ds.out_order[-2:] == io[-2:]
    canon = dense_step(ia, ib, io, _two, canonical=True)
    assert canon.a_shape == (1, 1024, 8) and canon.b_shape == (1, 8, 4)


# ----------------------------------------------------------------------
# the consumer's hint
# ----------------------------------------------------------------------
def _roles(m, n, kc_m, kc_n):
    """A step ``a(m) · b(n)`` (an outer product on one shared index
    ``k``) whose output the next step contracts over ``kc_m`` of ``m``
    and ``kc_n`` of ``n``; the K indices are spread through each
    operand's storage order."""
    ms = [f"m{j}" for j in range(m)]
    ns = [f"n{j}" for j in range(n)]
    kc = ms[:kc_m] + ns[:kc_n]
    a = tuple(ms[::2] + ["k"] + ms[1::2])
    b = tuple(ns[1::2] + ["k"] + ns[::2])
    return a, b, tuple(ms + ns), tuple(kc)


def _is_block(order, kc) -> bool:
    pos = sorted(order.index(ix) for ix in kc)
    return pos == list(range(pos[0], pos[0] + len(pos)))


@pytest.mark.parametrize("m,n,kc_m,kc_n", [
    (11, 11, 4, 4),  # M = N: no swap
    (12, 10, 5, 3),  # M > N
    (10, 12, 3, 5),  # N > M: the output is stored batch + N + M
    (9, 13, 2, 6),  # N > M, and under a lane above the block
])
@pytest.mark.parametrize("consumer_side", ["a", "b"])
def test_hint_makes_consumer_k_one_block(m, n, kc_m, kc_n, consumer_side):
    """With the hint the consumer's contracted indices, straddling M and
    N, are one block of the stored output, in the hint's order, and the
    other indices keep their operand's order.  The block and what lies
    below it are each at least a lane wide here, so the consumer's
    permute is one transpose; without the hint it takes more."""
    a, b, out, kc = _roles(m, n, kc_m, kc_n)
    hint = kc[::-1]
    ds = dense_step(a, b, out, _two, consumer_k=hint)
    assert ds.swap == (n > m)
    stored = ds.out_order
    assert sorted(stored) == sorted(out)
    assert _is_block(stored, kc)
    first, second = (b, a) if ds.swap else (a, b)
    assert [ix for ix in stored if ix in kc] == (
        [ix for ix in hint if ix in first]
        + [ix for ix in hint if ix in second]
    )
    for src in (a, b):
        rest = [ix for ix in stored if ix in src and ix not in kc]
        assert rest == [ix for ix in src if ix in rest]
    below = stored[max(stored.index(ix) for ix in kc) + 1:]
    assert 2 ** len(kc) >= LANE and 2 ** len(below) >= LANE
    # the consumer: this tensor against w(kc + z), contracting kc
    zs = tuple(f"z{j}" for j in range(7))
    w = kc + zs
    c_out = tuple(ix for ix in stored if ix not in kc) + zs
    unhinted = dense_step(a, b, out, _two).out_order
    passes = []
    for src in (stored, unhinted):
        lhs, rhs = (src, w) if consumer_side == "a" else (w, src)
        c = dense_step(lhs, rhs, c_out, _two)
        dst = c.a_gemm if consumer_side == "a" else c.b_gemm
        passes.append(len(transpose_orders(src, dst, _two)))
    assert passes[0] == 1
    assert passes[1] > 1


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("canonical", [False, True])
def test_dense_step_without_hint_keeps_operand_orders(seed, canonical):
    """No hint: each role keeps the order its operand stores it in, and
    the output is ``batch + M + N`` (``batch + N + M`` under ``swap``) —
    the layout every plan had before hints; an empty hint is the same."""
    rng = np.random.default_rng(seed)
    ia, ib, io = _random_step(rng, 1 + seed % 2, 3 + seed, 5 - seed % 3, 3)
    ds = dense_step(ia, ib, io, _two, canonical=canonical)
    sb, sa, so = set(ib), set(ia), set(io)
    batch = tuple(ix for ix in ia if ix in sb and ix in so)
    m = tuple(ix for ix in ia if ix not in sb)
    n = tuple(ix for ix in ib if ix not in sa)
    assert ds.out_order == (batch + n + m if ds.swap else batch + m + n)
    assert set(ds.a_gemm) == sa and set(ds.b_gemm) == sb
    assert tuple(ix for ix in ds.a_gemm if ix in m) == m
    assert tuple(ix for ix in ds.b_gemm if ix in n) == n
    assert dense_step(ia, ib, io, _two, canonical=canonical,
                      consumer_k=()) == ds


# ----------------------------------------------------------------------
# the plan's store orders
# ----------------------------------------------------------------------
def _network(rows, cols, cycles, k, seed=1):
    circ = sycamore_like(rows, cols, cycles, seed=seed)
    n = rows * cols
    if k:
        return open_batch_network(circ, "0" * n, tuple(range(n - k, n)))
    tn, arrays = circuit_to_network(circ, bitstring="0" * n)
    return simplify_network(tn, arrays)


# (rows, cols, cycles, open qubits, target width): the benchmark mixes'
# tiny copy (3x4x8 at width 7: amp, k = 6, k = 10) and networks whose
# tensors are wide enough that routes take several transposes
NETWORKS = [
    (3, 4, 8, 0, 7), (3, 4, 8, 6, 7), (3, 4, 8, 10, 11),
    (4, 4, 10, 0, 14), (4, 4, 12, 4, 14), (3, 5, 12, 0, 12),
    (4, 5, 12, 0, 14), (4, 4, 14, 2, 12),
]


@pytest.mark.parametrize("net", NETWORKS)
@pytest.mark.parametrize("seed", [1, 7])
def test_plan_guard_and_report_counters(net, seed):
    """The chosen orders model no more route bytes than the greedy
    ones, and the report's counters are a recount of the plan."""
    rows, cols, cycles, k, td = net
    tn, arrays = _network(rows, cols, cycles, k, seed)
    plan, report = plan_compiled(tn, td, dtype=arrays[0].dtype,
                                 backend="gemm", slicing_mode="peak",
                                 use_cache=False)
    _, _, greedy = greedy_layout(plan)
    chosen = layout_bytes(plan, plan.dense_steps, plan.store_order)
    assert report.permute_bytes == plan.permute_bytes == chosen
    assert report.permute_bytes_greedy == greedy
    assert chosen <= greedy
    hinted = 0
    for st, ds, spec in zip(plan.steps, plan.dense_steps,
                            plan.schedule.specs):
        alone = dense_step(plan.store_order[st.lhs],
                           plan.store_order[st.rhs], st.inds_out,
                           tn.size_of, canonical=spec.backend == "pallas")
        hinted += alone.out_order != ds.out_order
        assert (ds.a_order, ds.b_order) == (plan.store_order[st.lhs],
                                            plan.store_order[st.rhs])
        assert plan.store_order[st.out] == ds.out_order
    assert report.lookahead_orders == plan.lookahead_orders == hinted
    assert (hinted > 0) == (chosen < greedy)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("backend", ["gemm", "einsum"])
def test_plan_guard_on_random_trees(seed, backend):
    """Random regular networks under random trees, some indices sliced:
    the chosen orders model no more route bytes than the greedy ones."""
    tn = random_closed_network(18, 5 + seed % 2, seed)
    tree = random_tree(tn, seed)
    plan = ContractionPlan(tree, smask=(1 << (seed % 3)) - 1,
                           backend=backend)
    _, _, greedy = greedy_layout(plan)
    assert plan.permute_bytes_greedy == greedy
    assert plan.permute_bytes == layout_bytes(
        plan, plan.dense_steps, plan.store_order) <= greedy


def test_lookahead_engages_on_wide_networks():
    """The networks above whose routes take several transposes store
    some outputs for their consumer, and save route bytes by it."""
    saved = []
    for rows, cols, cycles, k, td in NETWORKS[3:]:
        tn, arrays = _network(rows, cols, cycles, k)
        plan, _ = plan_compiled(tn, td, dtype=arrays[0].dtype,
                                backend="einsum", slicing_mode="peak",
                                use_cache=False)
        assert plan.lookahead_orders > 0
        saved.append(1 - plan.permute_bytes / plan.permute_bytes_greedy)
    assert min(saved) > 0.1


def test_session_sets_plan_gauges():
    tn, arrays = _network(4, 4, 10, 0)
    plan, _ = plan_compiled(tn, 14, dtype=arrays[0].dtype, backend="gemm",
                            slicing_mode="peak", use_cache=False)
    metrics.reset()
    with trace.enabled_scope(True):
        ContractionSession(plan, arrays)
        snap = metrics.snapshot()
    gauges = snap["gauges"]
    assert gauges["plan.permute_bytes"] == plan.permute_bytes
    assert gauges["plan.permute_bytes_greedy"] == plan.permute_bytes_greedy
    assert gauges["plan.lookahead_orders"] == plan.lookahead_orders > 0


def _full_einsum(tn, arrays, out_inds):
    names: dict = {}
    operands = []
    for inds, arr in zip(tn.inputs, arrays):
        operands += [np.asarray(arr),
                     [names.setdefault(ix, len(names)) for ix in inds]]
    out = [names.setdefault(ix, len(names)) for ix in out_inds]
    return np.asarray(jnp.einsum(*operands, out,
                                 precision=jax.lax.Precision.HIGHEST))


@pytest.mark.parametrize("net", [(4, 4, 12, 4, 14), (3, 5, 12, 0, 12)])
@pytest.mark.parametrize("backend", ["gemm", "einsum"])
@pytest.mark.parametrize("hoist", [False, True])
def test_results_agree_with_greedy_orders(net, backend, hoist):
    """The same plan run with the chosen and with the greedy store
    orders gives the einsum reference's value to fp32 rounding."""
    rows, cols, cycles, k, td = net
    tn, arrays = _network(rows, cols, cycles, k)
    plan, _ = plan_compiled(tn, td, dtype=arrays[0].dtype, backend=backend,
                            slicing_mode="peak", use_cache=False)
    assert plan.lookahead_orders > 0 and plan.num_sliced > 0
    greedy = ContractionPlan(plan.tree, plan.smask, backend=backend,
                             dtype=plan.dtype)
    dense, order, _ = greedy_layout(greedy)
    greedy.dense_steps, greedy.store_order = dense, order
    assert greedy.store_order[greedy.root] != plan.store_order[plan.root] \
        or any(a.out_order != b.out_order
               for a, b in zip(dense, plan.dense_steps))
    want = _full_einsum(tn, arrays, plan.out_inds)
    scale = float(np.max(np.abs(want)))
    for p in (plan, greedy):
        sess = ContractionSession(p, arrays, hoist=hoist)
        assert sess.hoist == (hoist and p.can_hoist)
        got = np.asarray(sess.run_all(slice_batch=4))
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
