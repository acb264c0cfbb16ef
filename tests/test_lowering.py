"""GEMM lowering subsystem: normalization/refiner equivalence vs einsum,
end-to-end backend agreement, schedule execution under shard_map, and the
compiled-plan cache contract."""

import collections
import itertools
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import subprocess_kwargs
from repro.core import (
    ContractionPlan,
    default_backend,
    simplify_network,
    simulate_amplitude,
)
from repro.core.pathfinder import random_greedy_tree
from repro.core.slicing import find_slices
from repro.lowering import (
    GemmSpec,
    lower_step,
    refine_schedule,
    refine_step,
)
from repro.lowering import gemm_form, refiner
from repro.lowering.cache import PLAN_CACHE, PlanCache, network_fingerprint
from repro.quantum import statevector
from repro.quantum.circuits import circuit_to_network, random_1d_circuit

RNG = np.random.default_rng(0)


def _arrays_for(inds_a, inds_b, sizes, dtype):
    sa = tuple(sizes[ix] for ix in inds_a)
    sb = tuple(sizes[ix] for ix in inds_b)
    a = RNG.normal(size=sa)
    b = RNG.normal(size=sb)
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        a = a + 1j * RNG.normal(size=sa)
        b = b + 1j * RNG.normal(size=sb)
    return a.astype(dtype), b.astype(dtype)


def _check_equivalent(inds_a, inds_b, inds_out, sizes, dtype, spec=None,
                      tol=1e-4):
    form = lower_step(inds_a, inds_b, inds_out, sizes.__getitem__)
    if spec is None:
        spec = refine_step(form, dtype)
    else:
        spec = GemmSpec(form, spec, 128, 128, 128, 0.0, 0.0)
    a, b = _arrays_for(inds_a, inds_b, sizes, dtype)
    want = np.einsum(form.expr, a, b)
    got = np.asarray(gemm_form.apply(spec, jnp.asarray(a), jnp.asarray(b)))
    scale = max(1.0, np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)
    return spec


# ------------------------------------------------------- normalization
def test_index_classification():
    sizes = dict(b=2, m1=2, m2=3, n1=4, k1=2, k2=5)
    form = lower_step(
        ("b", "m1", "k1", "m2", "k2"),
        ("k2", "b", "n1", "k1"),
        ("b", "m1", "m2", "n1"),
        sizes.__getitem__,
    )
    assert form.batch_inds == ("b",)
    assert form.m_inds == ("m1", "m2")
    assert form.n_inds == ("n1",)
    assert form.k_inds == ("k1", "k2")
    assert (form.B, form.M, form.N, form.K) == (2, 6, 4, 10)
    assert form.flops == 2.0 * 2 * 6 * 4 * 10


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize(
    "inds_a,inds_b,inds_out,sizes",
    [
        # plain MxK @ KxN
        (("m", "k"), ("k", "n"), ("m", "n"), dict(m=4, k=8, n=4)),
        # batch (open sampling index shared by both operands)
        (("b", "m", "k"), ("k", "b", "n"), ("b", "m", "n"),
         dict(b=2, m=3, k=4, n=5)),
        # outer product: no contracted index (K = 1)
        (("m1", "m2"), ("n1",), ("m1", "m2", "n1"), dict(m1=2, m2=3, n1=4)),
        # full reduction to a scalar
        (("k1", "k2"), ("k2", "k1"), (), dict(k1=3, k2=4)),
        # interleaved output order (exercises out_perm)
        (("m", "k", "b"), ("n", "b", "k"), ("m", "b", "n"),
         dict(m=3, k=4, b=2, n=5)),
        # rank-0 operand against a matrix
        ((), ("n1", "n2"), ("n1", "n2"), dict(n1=2, n2=3)),
    ],
)
def test_lowered_step_matches_einsum(inds_a, inds_b, inds_out, sizes, dtype):
    _check_equivalent(inds_a, inds_b, inds_out, sizes, dtype)


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("backend", ["dot", "einsum"])
def test_forced_backends_match_einsum(dtype, backend):
    sizes = dict(b=2, m1=5, m2=7, n=33, k1=4, k2=9)
    _check_equivalent(
        ("b", "m1", "k1", "m2", "k2"), ("k2", "b", "n", "k1"),
        ("b", "m1", "m2", "n"), sizes, dtype, spec=backend,
    )


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
def test_pallas_backend_non_aligned(dtype):
    """Non-tile-aligned MXU-sized GEMM → Pallas with padding (+ Karatsuba
    for complex), interpret mode on CPU."""
    sizes = dict(m=130, k=140, n=150)
    spec = _check_equivalent(
        ("m", "k"), ("k", "n"), ("m", "n"), sizes, dtype, tol=1e-5
    )
    assert spec.backend == "pallas"
    assert spec.bm % 128 == 0 and spec.bn % 128 == 0 and spec.bk % 128 == 0
    assert 0.0 < spec.pad_waste < 1.0


def test_pallas_step_under_vmap():
    """The refined Pallas step must run inside the executor's slice-batch
    vmap."""
    sizes = dict(m=130, k=140, n=150)
    form = lower_step(("m", "k"), ("k", "n"), ("m", "n"), sizes.__getitem__)
    spec = refine_step(form, np.complex64)
    assert spec.backend == "pallas"
    a, b = _arrays_for(("m", "k"), ("k", "n"), sizes, np.complex64)
    va = jnp.stack([jnp.asarray(a), 2.0 * jnp.asarray(a)])
    vb = jnp.stack([jnp.asarray(b), jnp.asarray(b)])
    got = jax.vmap(lambda x, y: gemm_form.apply(spec, x, y))(va, vb)
    np.testing.assert_allclose(
        np.asarray(got[1]), 2.0 * (a @ b), rtol=0,
        atol=1e-5 * np.abs(a @ b).max(),
    )


def test_pallas_spec_adapts_to_64bit_arrays():
    """A schedule refined for complex64 handed complex128 arrays at
    runtime must not silently truncate through the fp32 Pallas path."""
    jax.config.update("jax_enable_x64", True)
    try:
        sizes = dict(m=130, k=140, n=150)
        form = lower_step(("m", "k"), ("k", "n"), ("m", "n"),
                          sizes.__getitem__)
        spec = refine_step(form, np.complex64)
        assert spec.backend == "pallas"
        a, b = _arrays_for(("m", "k"), ("k", "n"), sizes, np.complex128)
        got = np.asarray(
            gemm_form.apply(spec, jnp.asarray(a), jnp.asarray(b))
        )
        assert got.dtype == np.complex128
        np.testing.assert_allclose(got, a @ b, rtol=0,
                                   atol=1e-10 * np.abs(a @ b).max())
    finally:
        jax.config.update("jax_enable_x64", False)


def test_refiner_routes_64bit_off_pallas():
    sizes = dict(m=256, k=256, n=256)
    form = lower_step(("m", "k"), ("k", "n"), ("m", "n"), sizes.__getitem__)
    assert refine_step(form, np.float32).backend == "pallas"
    assert refine_step(form, np.float64).backend == "dot"
    assert refine_step(form, np.complex128).backend == "dot"


# (M, N, K) of the Pallas steps of the benchmark's 30-qubit plans
STEM_SHAPES = [
    (32768, 2048, 256), (1024, 2048, 65536), (512, 32768, 4096),
    (32768, 4096, 512), (16384, 2048, 8192), (32768, 4096, 256),
    (32768, 512, 4096), (4096, 4096, 4096), (65536, 512, 256),
    (32768, 2048, 1024), (8192, 8192, 8192),
]


def _mnk_form(m, n, k):
    sizes = dict(m=m, n=n, k=k)
    return lower_step(("m", "k"), ("k", "n"), ("m", "n"), sizes.__getitem__)


def _admissible_tiles(form, dtype):
    """Tiles within the VMEM budget that pad no dimension past 128."""
    ob = gemm_form.real_component_bytes(dtype)
    for t in itertools.product(refiner.BLOCK_CANDIDATES, repeat=3):
        bm, bn, bk = t
        if ob * (bm * bk + bk * bn) + 4 * bm * bn > (
            refiner.VMEM_BUDGET_BYTES
        ):
            continue
        if all(-(-d // b) * b == -(-d // 128) * 128
               for d, b in zip((form.M, form.N, form.K), t)):
            yield t


@pytest.mark.parametrize("dtype", [np.complex64, np.float32])
@pytest.mark.parametrize("m,n,k", STEM_SHAPES)
def test_refiner_picks_fewest_grid_steps_on_stem_shapes(m, n, k, dtype):
    form = _mnk_form(m, n, k)
    spec = refine_step(form, dtype)
    assert spec.backend == "pallas"
    assert spec.pad_waste == 0.0
    assert (spec.bm, spec.bn, spec.bk) == (512, 512, min(k, 512))
    steps = refiner.pallas_grid_steps(form, dtype, spec.bm, spec.bn, spec.bk)
    assert steps == min(
        refiner.pallas_grid_steps(form, dtype, *t)
        for t in _admissible_tiles(form, dtype)
    )


def test_refiner_does_not_pad_ragged_m_for_fewer_grid_steps():
    """M = 5 x 128: a 512-row tile would pad M to 1024 for fewer grid
    steps; padding outweighs them."""
    form = _mnk_form(640, 4096, 4096)
    spec = refine_step(form, np.complex64)
    assert spec.backend == "pallas"
    assert -(-640 // spec.bm) * spec.bm == 640
    assert spec.pad_waste == 0.0
    assert (spec.bn, spec.bk) == (512, 512)


def test_pallas_grid_steps_counts_every_pallas_step():
    sizes = dict(b=2, m=640, k=1024, n=512, p=4096, q=8)
    sched = refine_schedule(
        [
            (("b", "m", "k"), ("b", "k", "n"), ("b", "m", "n")),
            (("n", "p"), ("p", "q", "m"), ("n", "q", "m")),
        ],
        sizes.__getitem__,
        dtype=np.complex64,
    )
    assert sched.backend_counts() == {"pallas": 2}
    want = 0
    for s in sched.specs:
        f = s.form
        want += (f.B * -(-f.M // s.bm) * -(-f.N // s.bn)
                 * -(-f.K // s.bk) * 3)  # Karatsuba's 3 real GEMMs
    assert sched.pallas_grid_steps() == want > 0
    tiles = sched.pallas_tiles()
    assert tiles == dict(collections.Counter(
        f"{s.bm}×{s.bn}×{s.bk}" for s in sched.specs
    ))
    summary = sched.summary()
    assert summary["pallas_grid_steps"] == want
    assert summary["pallas_tiles"] == tiles
    assert f"grid_steps={want}" in sched.summary_row()


@given(
    seed=st.integers(0, 10_000),
    nb=st.integers(0, 2),
    nm=st.integers(0, 2),
    nn=st.integers(0, 2),
    nk=st.integers(0, 2),
    complex_=st.booleans(),
)
@settings(max_examples=40)
def test_lowering_property(seed, nb, nm, nn, nk, complex_):
    """Random pairwise contractions (random role counts, sizes 1..5,
    shuffled axis orders, complex + real dtypes) — lowered GEMM path ==
    einsum."""
    rng = np.random.default_rng(seed)
    batch = [f"b{i}" for i in range(nb)]
    ms = [f"m{i}" for i in range(nm)]
    ns = [f"n{i}" for i in range(nn)]
    ks = [f"k{i}" for i in range(nk)]
    sizes = {ix: int(rng.integers(1, 6)) for ix in batch + ms + ns + ks}
    inds_a = batch + ms + ks
    inds_b = batch + ks + ns
    rng.shuffle(inds_a)
    rng.shuffle(inds_b)
    from repro.core.executor import pair_contract_inds

    _, inds_out = pair_contract_inds(
        tuple(inds_a), tuple(inds_b), frozenset(batch)
    )
    dtype = np.complex64 if complex_ else np.float32
    _check_equivalent(tuple(inds_a), tuple(inds_b), inds_out, sizes, dtype)


# ------------------------------------------------------- schedule + e2e
def test_refine_schedule_summary():
    sizes = dict(m=130, k=140, n=150, p=8)
    sched = refine_schedule(
        [
            (("m", "k"), ("k", "n"), ("m", "n")),
            (("m", "p"), ("p",), ("m",)),
        ],
        sizes.__getitem__,
        dtype=np.complex64,
    )
    s = sched.summary()
    assert s["nodes"] == 2
    assert s["backends"]["pallas"] == 1
    assert s["backends"]["einsum"] == 1
    assert 0.0 < s["pad_waste"] < 1.0
    assert sched.modeled_time_s > 0
    assert "pallas=1" in sched.summary_row()


def test_simulate_backend_agreement():
    """simulate(backend='gemm') == simulate(backend='einsum') == oracle,
    sliced + vmapped slice batching included."""
    c = random_1d_circuit(9, 7, seed=11)
    bs = "011010010"
    ref = statevector.amplitude(c, bs)
    r_e = simulate_amplitude(c, bs, target_dim=4, backend="einsum",
                             use_cache=False)
    r_g = simulate_amplitude(c, bs, target_dim=4, backend="gemm",
                             use_cache=False)
    assert r_g.report.backend == "gemm"
    assert r_g.report.num_sliced > 0  # vmapped slice batching exercised
    assert r_g.plan is not None and r_g.plan.schedule is not None
    assert sum(r_g.plan.schedule.backend_counts().values()) == len(
        r_g.plan.schedule.specs
    )
    assert abs(complex(r_g.value) - complex(r_e.value)) < 1e-5
    assert abs(complex(r_g.value) - ref) < 1e-4
    assert "backend=gemm" in r_g.report.row()


def test_gemm_plan_dense_and_sliced_agree():
    c = random_1d_circuit(10, 8, seed=3)
    tn, arrays = circuit_to_network(c, bitstring="0110100101")
    tn, arrays = simplify_network(tn, arrays)
    tree = random_greedy_tree(tn, repeats=4)
    dense = np.asarray(ContractionPlan(tree, 0).contract_all(arrays))
    S = find_slices(tree, 4, method="lifetime")
    v = np.asarray(
        ContractionPlan(tree, S, backend="gemm").contract_all(
            arrays, slice_batch=4
        )
    )
    np.testing.assert_allclose(v, dense, rtol=1e-4, atol=1e-5)


SHARDED_GEMM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
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
dense = ContractionPlan(tree, 0).contract_all(arrays)
plan = ContractionPlan(tree, S, backend="gemm")
assert plan.schedule is not None
mesh = make_host_mesh((4,), ("data",))
v = contract_sharded(plan, arrays, mesh, axis_names=("data",), slice_batch=2)
assert np.allclose(np.asarray(v), np.asarray(dense), atol=1e-5)
# second call reuses the memoized shard_map program
v2 = contract_sharded(plan, arrays, mesh, axis_names=("data",), slice_batch=2)
assert np.allclose(np.asarray(v2), np.asarray(dense), atol=1e-5)
assert any(k[0] == "sharded" for k in plan._compiled)
print("DONE")
"""


def test_contract_sharded_gemm_schedule():
    """The lowered schedule threads through shard_map unchanged."""
    r = subprocess.run(
        [sys.executable, "-c", SHARDED_GEMM],
        capture_output=True, text=True, timeout=900,
        **subprocess_kwargs(),
    )
    assert "DONE" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]


def test_sampling_backend_agreement():
    from repro.core import sample_bitstrings

    c = random_1d_circuit(8, 6, seed=5)
    r_e = sample_bitstrings(c, num_samples=32, open_qubits=(5, 6, 7),
                            target_dim=5, backend="einsum", use_cache=False)
    r_g = sample_bitstrings(c, num_samples=32, open_qubits=(5, 6, 7),
                            target_dim=5, backend="gemm", use_cache=False)
    np.testing.assert_allclose(
        r_g.batch.amplitudes, r_e.batch.amplitudes, rtol=0, atol=1e-5
    )
    assert r_g.report.backend == "gemm"


# ------------------------------------------------------------- caching
def test_fingerprint_relabel_invariance():
    from repro.core import TensorNetwork

    tn1 = TensorNetwork([("a", "b"), ("b", "c")], open_inds=("c",))
    tn2 = TensorNetwork([("x", "y"), ("y", "z")], open_inds=("z",))
    tn3 = TensorNetwork([("a", "b"), ("b", "c")], open_inds=())
    assert network_fingerprint(tn1, "complex64") == network_fingerprint(
        tn2, "complex64"
    )
    assert network_fingerprint(tn1, "complex64") != network_fingerprint(
        tn3, "complex64"
    )
    assert network_fingerprint(tn1, "complex64") != network_fingerprint(
        tn1, "float32"
    )
    assert network_fingerprint(tn1, "complex64", extra=("gemm",)) != (
        network_fingerprint(tn1, "complex64", extra=("einsum",))
    )


def test_plan_cache_hit_miss():
    """Repeated simulate on the same circuit: first call misses, second
    hits, plan wall time drops, and the identical plan object is reused."""
    PLAN_CACHE.clear()
    c = random_1d_circuit(9, 7, seed=23)
    bs1, bs2 = "010110100", "111000101"
    r1 = simulate_amplitude(c, bs1, target_dim=4, backend="gemm")
    assert not r1.report.cache_hit
    assert r1.report.cache_misses >= 1
    # different bitstring, same structure → still a hit
    r2 = simulate_amplitude(c, bs2, target_dim=4, backend="gemm")
    assert r2.report.cache_hit
    assert r2.report.cache_hits >= 1
    assert r2.plan is r1.plan
    assert r2.report.plan_wall_s < r1.report.plan_wall_s
    # cached plan still yields correct values
    ref = statevector.amplitude(c, bs2)
    assert abs(complex(r2.value) - ref) < 1e-4
    # backend is part of the key: einsum request must not reuse gemm plan
    r3 = simulate_amplitude(c, bs1, target_dim=4, backend="einsum")
    assert not r3.report.cache_hit
    # opting out bypasses the cache entirely
    r4 = simulate_amplitude(c, bs1, target_dim=4, backend="gemm",
                            use_cache=False)
    assert not r4.report.cache_hit
    # search budgets only shape anytime plans: under oneshot they are
    # left out of the key, so they cannot split the cache ...
    r5 = simulate_amplitude(c, bs1, target_dim=4, backend="gemm",
                            search_evals=3, search_workers=1)
    assert r5.report.cache_hit and r5.plan is r1.plan
    # ... while under anytime they separate plans
    ra = simulate_amplitude(c, bs1, target_dim=4, backend="gemm",
                            optimize="anytime", search_evals=3,
                            search_workers=1)
    rb = simulate_amplitude(c, bs1, target_dim=4, backend="gemm",
                            optimize="anytime", search_evals=4,
                            search_workers=1)
    assert not ra.report.cache_hit and not rb.report.cache_hit
    assert rb.plan is not ra.plan
    # every other option joins the key
    r6 = simulate_amplitude(c, bs1, target_dim=4, backend="gemm",
                            repeats=3)
    assert not r6.report.cache_hit


def test_plan_cache_lru_eviction():
    cache = PlanCache(maxsize=2)
    cache.put("a", "A")
    cache.put("b", "B")
    assert cache.get("a").__class__ is str  # touch a → b becomes LRU
    cache.put("c", "C")
    assert cache.get("b") is None
    assert len(cache) == 2
    stats = cache.stats()
    assert stats["hits"] == 1 and stats["misses"] == 1


# ---------------------------------------------------------- satellites
def test_kernels_package_root_exports():
    from repro.kernels import (  # noqa: F401
        attention,
        flash_attention,
        matmul,
        ssd_intra_chunk,
        ssd_scan,
        tiled_matmul,
    )


def test_default_backend_env(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert default_backend() == "einsum"
    monkeypatch.setenv("REPRO_BACKEND", "gemm")
    assert default_backend() == "gemm"
    monkeypatch.setenv("REPRO_BACKEND", "bogus")
    with pytest.raises(ValueError):
        default_backend()
