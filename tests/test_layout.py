"""Lane-dense storage: permutation routes and per-step GEMM layouts.

Checks that every route of :func:`repro.lowering.layout.transpose_orders`
ends at the requested order, moves data exactly like a numpy transpose,
and keeps a minor group of the promised width on both sides of each of
its transposes; and that :func:`repro.lowering.layout.dense_step` plus
:func:`repro.lowering.gemm_form.contract_flat` contract exactly like
``einsum`` in every orientation it picks.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import einsum_expr, pair_contract_inds
from repro.lowering import gemm_form
from repro.lowering.layout import (
    LANE,
    _dense_move,
    _lane,
    dense_step,
    permute_flat,
    transpose_orders,
)


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
def test_dense_step_matches_einsum(nb, nm, nn, nk, complex_):
    rng = np.random.default_rng(nb + 3 * nm + 7 * nn + 11 * nk)
    ia, ib, io = _random_step(rng, nb, nm, nn, nk)
    ds = dense_step(ia, ib, io, _two)
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
