"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed next to JAX, and it compiles for a chip
that is described rather than attached.  These tests compile the Pallas
GEMM kernel at the tile shapes the refiner emits and whole slice
programs of small gemm and einsum plans, and assert that the kernel is
really in the program (``tpu_custom_call``) and that the compiled
footprint stays near the lifetime planner's certified peak.  Nothing
runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.api import plan_compiled
from repro.core.executor import simplify_network
from repro.engine.session import ContractionSession
from repro.kernels import ops, tiled_matmul
from repro.lowering import gemm_form, lower_step, refine_step
from repro.lowering.layout import transpose_orders
from repro.lowering.refiner import BLOCK_CANDIDATES, VMEM_BUDGET_BYTES
from repro.quantum.circuits import circuit_to_network, sycamore_like


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compile_for_chip(monkeypatch):
    """Kernels compile for the chip (not the interpreter), and the
    persistent cache is off: an entry compiled for a described chip
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


# the (bm, bn, bk) tiles the refiner may emit at fp32
REFINER_TILES = [
    t for t in itertools.product(BLOCK_CANDIDATES, repeat=3)
    if 4 * (t[0] * t[2] + t[2] * t[1]) + 4 * t[0] * t[1] <= VMEM_BUDGET_BYTES
]


@pytest.mark.parametrize("bm,bn,bk", REFINER_TILES)
def test_tiled_matmul_compiles_at_refiner_tiles(one_chip, bm, bn, bk):
    a = _sds((2 * bm, 2 * bk), jnp.float32, one_chip)
    b = _sds((2 * bk, 2 * bn), jnp.float32, one_chip)
    fn = jax.jit(lambda x, y: tiled_matmul(x, y, bm=bm, bn=bn, bk=bk))
    text = fn.lower(a, b).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_complex_karatsuba_matmul_compiles(one_chip, precision):
    a = _sds((256, 384), jnp.complex64, one_chip)
    b = _sds((384, 128), jnp.complex64, one_chip)
    fn = jax.jit(lambda x, y: ops.matmul(x, y, precision=precision))
    text = fn.lower(a, b).compile().as_text()
    assert text.count("tpu_custom_call") >= 3  # three real GEMMs


# (M, N, K) of the two costliest stem GEMMs of the benchmark's plans
@pytest.mark.parametrize("m,n,k", [(16384, 2048, 8192), (8192, 8192, 8192)])
def test_karatsuba_matmul_compiles_at_real_size_and_chosen_tile(
    one_chip, m, n, k
):
    """The kernel at the tile the refiner chooses for the cells' largest
    steps, at their full size: Mosaic must accept the VMEM it needs."""
    form = lower_step(("m", "k"), ("k", "n"), ("m", "n"),
                      dict(m=m, n=n, k=k).__getitem__)
    spec = refine_step(form, jnp.complex64)
    assert spec.backend == "pallas"
    a = _sds((m, k), jnp.complex64, one_chip)
    b = _sds((k, n), jnp.complex64, one_chip)
    fn = jax.jit(lambda x, y: ops.matmul(x, y, bm=spec.bm, bn=spec.bn,
                                         bk=spec.bk))
    text = fn.lower(a, b).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 3  # three real GEMMs


def _slice_program(plan, arrays, sharding, batch):
    sess = ContractionSession(plan, arrays)
    hshapes = (
        jax.eval_shape(plan._prologue_outputs, list(arrays))
        if sess.hoist else []
    )
    args = (
        [_sds(np.shape(a), np.asarray(a).dtype, sharding) for a in arrays],
        [_sds(h.shape, h.dtype, sharding) for h in hshapes],
        _sds((batch, plan.num_sliced), jnp.int32, sharding),
        _sds((batch,), jnp.bool_, sharding),
    )
    return sess._batch_fn().lower(*args).compile()


@pytest.fixture(scope="module")
def syc12():
    circ = sycamore_like(4, 5, 12)
    tn, arrays = circuit_to_network(circ, bitstring="0" * circ.num_qubits)
    return simplify_network(tn, arrays)


@pytest.mark.parametrize("backend", ["gemm", "einsum"])
def test_slice_program_compiles_near_certified_peak(one_chip, syc12, backend):
    tn, arrays = syc12
    plan, _ = plan_compiled(
        tn, 18, dtype=arrays[0].dtype, backend=backend,
        slicing_mode="peak", use_cache=False,
    )
    compiled = _slice_program(plan, arrays, one_chip, batch=2)
    text = compiled.as_text()
    if backend == "gemm":
        assert plan.schedule.backend_counts().get("pallas")
        assert "tpu_custom_call" in text
    else:
        assert "tpu_custom_call" not in text
    ma = compiled.memory_analysis()
    certified = 2 * plan.memory_plan().peak_bytes
    # one axis per index would pad every minor dim of 2 to 128 lanes
    assert ma.temp_size_in_bytes <= 4 * certified


def _code(hlo_text: str) -> list[str]:
    """HLO text without metadata and the stack-frame tables it uses."""
    text = re.sub(r", metadata=\{[^}]*\}", "", hlo_text)
    return [
        line for line in text.splitlines()
        if not re.match(r"(\d+ |FileNames|FunctionNames|FileLocations|"
                        r"StackFrames)", line)
    ]


def test_step_scopes_change_metadata_only(one_chip, syc12, monkeypatch):
    """The slice program's named scopes leave the chip's program as it
    is, and put every Pallas kernel, named ``tiled_matmul``, in the
    ``gemm`` scope of a ``pallas`` step."""
    tn, arrays = syc12

    def compiled_text():
        plan, _ = plan_compiled(
            tn, 18, dtype=arrays[0].dtype, backend="gemm",
            slicing_mode="peak", use_cache=False,
        )
        return _slice_program(plan, arrays, one_chip, batch=2).as_text()

    scoped = compiled_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_text()
    assert _code(scoped) == _code(bare)
    kernels = [line for line in scoped.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels
    for line in kernels:
        assert re.match(r"\s*(ROOT )?%tiled_matmul(\.\d+)? = ", line), line
        assert re.search(r'op_name="[^"]*\bstep\d+\.pallas\)?/gemm/',
                         line), line


def test_sharded_program_compiles_for_four_chips(topo, syc12):
    from jax.sharding import Mesh

    tn, arrays = syc12
    plan, _ = plan_compiled(
        tn, 18, dtype=arrays[0].dtype, backend="gemm",
        slicing_mode="peak", use_cache=False,
    )
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())
    shard = NamedSharding(mesh, PartitionSpec("data"))
    sess = ContractionSession(plan, arrays)
    hshapes = (
        jax.eval_shape(plan._prologue_outputs, list(arrays))
        if sess.hoist else []
    )
    args = (
        [_sds(np.shape(a), np.asarray(a).dtype, rep) for a in arrays],
        [_sds(h.shape, h.dtype, rep) for h in hshapes],
        _sds((8, plan.num_sliced), jnp.int32, shard),
        _sds((8,), jnp.bool_, shard),
    )
    compiled = sess._sharded_fn(mesh, ("data",), 1).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


def _copies_in(text: str, scope: str) -> int:
    """Transposes of the compiled program inside ``scope``'s ``permute``
    (on a TPU each is a layout-changing ``copy``)."""
    return sum(
        1 for line in text.splitlines()
        if re.match(r"\s*(ROOT )?%\S+ = \S+ copy\(", line)
        and f"/{scope}/permute/transpose" in line
    )


def test_step_pair_stores_for_its_consumer(one_chip):
    """The 30-qubit, 16-cycle Sycamore plan of the benchmark's
    ``syc30.amp``: step 143 writes step 168's 2^27-element ``a``.  With
    each step choosing its store order alone, step 168 permutes it in
    five transposes; stored for step 168, in three.  Compiled at the
    real shapes, step 168's permute of ``a`` has fewer transposes and
    the pair fewer temporary bytes."""
    from conftest import greedy_layout

    circ = sycamore_like(5, 6, 16)
    tn, arrays = circuit_to_network(circ, bitstring="0" * circ.num_qubits)
    tn, arrays = simplify_network(tn, arrays)
    plan, _ = plan_compiled(tn, 26, dtype=arrays[0].dtype, backend="gemm",
                            slicing_mode="peak", use_cache=False)
    p, c = 143, 168
    assert plan.steps[c].lhs == plan.steps[p].out
    greedy, _, _ = greedy_layout(plan)
    copies, temps = [], []
    for dense in (greedy, plan.dense_steps):
        dp = dense[p]
        # step 168's b arrives in its GEMM order: every transpose left
        # in step 168 is a's
        dc = dataclasses.replace(dense[c], b_order=dense[c].b_gemm)
        n = dc.a_shape[0] * dc.a_shape[1] * dc.a_shape[2]
        assert n == 2 ** 27
        routes = len(transpose_orders(dc.a_order, dc.a_gemm, dc.size_of))
        assert routes == (5 if dense is greedy else 3)

        def pair(a, b, y, dp=dp, dc=dc):
            with jax.named_scope(f"step{p}"):
                x = gemm_form.contract_flat(plan.schedule.specs[p], dp, a, b)
            with jax.named_scope(f"step{c}"):
                return gemm_form.contract_flat(plan.schedule.specs[c], dc,
                                               x, y)

        args = [
            _sds((int(np.prod([dp.size_of(i) for i in o])),),
                 jnp.complex64, one_chip)
            for o in (dp.a_order, dp.b_order)
        ] + [_sds((int(np.prod(dc.b_shape)),), jnp.complex64, one_chip)]
        compiled = jax.jit(pair).lower(*args).compile()
        copies.append(_copies_in(compiled.as_text(), f"step{c}"))
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
    assert 0 < copies[1] < copies[0]
    assert temps[1] <= temps[0]
