"""The plain reference against numpy at small sizes."""

import itertools
import random

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R


@pytest.mark.parametrize("k,seed", [(6, 0), (12, 1), (16, 2), (22, 3)])
def test_permute_matches_numpy(k, seed):
    rng = random.Random(seed)
    src = [f"i{j}" for j in range(k)]
    for _ in range(4):
        dst = src[:]
        rng.shuffle(dst)
        x = np.arange(2 ** k, dtype=np.float32)
        want = np.transpose(
            x.reshape((2,) * k), [src.index(a) for a in dst]
        ).reshape(-1)
        got = np.asarray(R.permute(jnp.asarray(x), src, dst))
        np.testing.assert_array_equal(got, want)
        for a, b in itertools.pairwise([tuple(src)] + R.route(src, dst)):
            w = min(R.LANE_BITS, k // 3)
            assert k < 10 or R._dense(a, b, w) or b == tuple(dst)


def test_contract_pair_matches_einsum():
    rng = np.random.default_rng(0)
    a_ord = ("a", "b", "c", "d", "e")
    b_ord = ("d", "f", "b", "g")
    a = rng.normal(size=32) + 1j * rng.normal(size=32)
    b = rng.normal(size=16) + 1j * rng.normal(size=16)
    for mode in ("highest", "bf16x3"):
        lay = R.pair_layout(a_ord, b_ord, frozenset())
        out = R.contract_pair(jnp.asarray(a, jnp.complex64), a_ord,
                              jnp.asarray(b, jnp.complex64), b_ord, lay, mode)
        order = lay["out"]
        want = np.einsum("abcde,dfbg->" + "".join(order),
                         a.reshape((2,) * 5), b.reshape((2,) * 4))
        np.testing.assert_allclose(np.asarray(out), want.reshape(-1),
                                   rtol=1e-3 if mode == "bf16x3" else 1e-5)


def test_bf16x3_is_coarser_than_highest():
    rng = np.random.default_rng(1)
    a = rng.normal(size=(1, 64, 64)).astype(np.float32)
    b = rng.normal(size=(1, 64, 64)).astype(np.float32)
    want = np.einsum("bij,bjk->bik", a.astype(np.float64), b)
    dims = (((2,), (1,)), ((0,), (0,)))
    errs = {
        m: np.max(np.abs(np.asarray(R._dot(
            jnp.asarray(a + 0j, jnp.complex64),
            jnp.asarray(b + 0j, jnp.complex64), dims, m)).real - want))
        for m in ("highest", "bf16x3")
    }
    assert errs["bf16x3"] > 10 * errs["highest"]
