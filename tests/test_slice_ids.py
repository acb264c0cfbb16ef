"""Slice ids wider than 31 bits on the normal path.

The host keeps a slice id as an int of up to ``MAX_SLICE_BITS`` bits and
hands the device its bits (``ContractionPlan.slice_bits``), one column per
sliced index.  A small Sycamore-recipe network planned at a low target
width slices more than 32 indices, so its ids pass ``2**31``; every
slice the engine runs there is compared with a plain ``jnp.einsum`` of
the same leaves, the sliced wires fixed by hand.
"""

from __future__ import annotations

import functools
import json
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import subprocess_kwargs
from repro.core.api import plan_compiled
from repro.core.executor import MAX_SLICE_BITS, ContractionPlan
from repro.engine.session import ContractionSession
from repro.obs import metrics, trace
from repro.quantum.circuits import sycamore_like
from repro.sampling.batch import open_batch_network

# 4x4 grid, 14 cycles, two open qubits, planned at width 4: 35 sliced
# indices and a slice-invariant prologue to hoist
ROWS, COLS, CYCLES, SEED, OPEN, TARGET_DIM = 4, 4, 14, 5, (14, 15), 4

# the slice ids run here: each has bits above 31 set
WIDE_IDS = [2**31, 2**31 + 1, 2**33 + 5, 2**34 + 2**32 + 77, 2**35 - 2,
            2**35 - 1]

# Each slice is about 60 pairwise complex64 contractions of values of
# order one; the engine and the einsum contract in different orders, so
# the two agree to float32 rounding times the depth, well under 1e-5 of
# the slice's largest magnitude.  A wrong bit picks another slice and
# misses by the whole value.
REL_TOL = 1e-5


@functools.lru_cache(maxsize=None)
def wide_plan():
    """The plan (gemm backend, peak slicing) and its leaf arrays."""
    circ = sycamore_like(ROWS, COLS, CYCLES, seed=SEED)
    tn, arrays = open_batch_network(circ, "0" * circ.num_qubits, OPEN)
    plan, _ = plan_compiled(
        tn, TARGET_DIM, backend="gemm", slicing_mode="peak", use_cache=False
    )
    return plan, tuple(np.asarray(a) for a in arrays)


def reference_slice(plan, arrays, slice_id: int) -> np.ndarray:
    """One slice by ``jnp.einsum`` at ``HIGHEST``: each leaf's sliced
    wires fixed to the id's bits (bit ``j`` for ``plan.sliced_bits[j]``),
    then one contraction of all leaves into ``plan.out_inds``."""
    labels = plan.tn.space.labels
    value = {labels[b]: (slice_id >> j) & 1
             for j, b in enumerate(plan.sliced_bits)}
    names: dict = {}
    operands = []
    for inds, arr in zip(plan.tn.inputs, arrays):
        a = np.asarray(arr)
        for ax in reversed(range(len(inds))):
            if inds[ax] in value:
                a = np.take(a, value[inds[ax]], axis=ax)
        keep = [ix for ix in inds if ix not in value]
        operands += [a, [names.setdefault(ix, len(names)) for ix in keep]]
    out = [names.setdefault(ix, len(names)) for ix in plan.out_inds]
    return np.asarray(
        jnp.einsum(*operands, out, precision=jax.lax.Precision.HIGHEST)
    )


def assert_close(got, want) -> None:
    want = np.asarray(want)
    scale = float(np.max(np.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=0, atol=REL_TOL * scale
    )


# ----------------------------------------------------------------------
# the encoding
# ----------------------------------------------------------------------
class _Plan:
    """Only what ``slice_bits`` reads of a plan."""

    slice_bits = ContractionPlan.slice_bits

    def __init__(self, num_sliced):
        self.num_sliced = num_sliced


@pytest.mark.parametrize("slice_id", [
    0, 2**31 - 1, 2**31, 2**32 + 3, 2**47 + 2**40 + 12345,
    2**61 + 2**31 + 1, 2**62 - 1, 0x2AAAAAAAAAAAAAAA, 0x1555555555555555,
])
def test_bits_match_python_shifts(slice_id):
    plan = _Plan(MAX_SLICE_BITS)
    want = [(slice_id >> j) & 1 for j in range(MAX_SLICE_BITS)]
    for ids in (slice_id, np.int64(slice_id), np.uint64(slice_id)):
        got = plan.slice_bits(ids)
        assert got.dtype == np.int32 and got.tolist() == want
    batch = plan.slice_bits([slice_id, 5])
    assert batch.shape == (2, MAX_SLICE_BITS)
    assert batch[0].tolist() == want


def test_bits_of_a_batch_and_of_no_sliced_index():
    ids = np.array(WIDE_IDS, dtype=np.uint64)
    got = _Plan(35).slice_bits(ids)
    assert got.shape == (len(WIDE_IDS), 35)
    assert got.tolist() == [[(i >> j) & 1 for j in range(35)]
                            for i in WIDE_IDS]
    assert _Plan(0).slice_bits([3, 4]).shape == (2, 0)


def test_more_sliced_indices_than_an_id_holds_is_refused():
    with pytest.raises(ValueError, match="at most 62 bits"):
        _Plan(MAX_SLICE_BITS + 1).slice_bits(0)


# ----------------------------------------------------------------------
# the engine on ids past 2**31
# ----------------------------------------------------------------------
def test_plan_slices_more_than_32_indices():
    plan, _ = wide_plan()
    assert 32 <= plan.num_sliced <= MAX_SLICE_BITS
    assert plan.can_hoist
    assert max(WIDE_IDS) < 1 << plan.num_sliced


@pytest.mark.parametrize("hoist", [False, True])
def test_run_slices_past_2_31_slice_by_slice(hoist):
    plan, arrays = wide_plan()
    sess = ContractionSession(plan, list(arrays), hoist=hoist)
    assert sess.hoist == hoist
    refs = {i: reference_slice(plan, arrays, i) for i in WIDE_IDS}
    for i in WIDE_IDS:
        assert_close(sess.run_slices([i]), refs[i])
    # a batch with a masked lane: the sum of the valid slices only
    ids = WIDE_IDS[:4]
    valid = np.array([True, True, False, True])
    want = sum(refs[i] for i, ok in zip(ids, valid) if ok)
    assert_close(sess.run_slices(ids, valid), want)


def test_run_slice_past_2_31():
    plan, arrays = wide_plan()
    sess = ContractionSession(plan, list(arrays), hoist=True)
    i = WIDE_IDS[-2]
    assert_close(sess.run_slice(i), reference_slice(plan, arrays, i))


def test_compiled_slices_takes_a_row_of_bits_per_id():
    plan, arrays = wide_plan()
    sess = ContractionSession(plan, list(arrays), hoist=True)
    compiled = sess.compiled_slices(2)
    assert compiled.memory_analysis() is not None
    shapes = [a.shape for a in jax.tree_util.tree_leaves(compiled.args_info)]
    assert shapes[-2:] == [(2, plan.num_sliced), (2,)]


@functools.lru_cache(maxsize=None)
def narrow_plan(target_dim: int):
    """A 3x4, 10-cycle amplitude network planned at ``target_dim``:
    width 6 slices 8 indices, width 4 slices 13."""
    circ = sycamore_like(3, 4, 10, seed=SEED)
    tn, arrays = open_batch_network(circ, "0" * circ.num_qubits, ())
    plan, _ = plan_compiled(
        tn, target_dim, backend="gemm", slicing_mode="peak", use_cache=False
    )
    return plan, tuple(np.asarray(a) for a in arrays)


@pytest.mark.parametrize("target_dim", [6, 4])
def test_run_all_takes_the_ids_bits_as_an_argument(target_dim):
    """The bits of all ``2^|S|`` ids, ``4 |S| 2^|S|`` bytes, reach the
    scan as an argument: no constant of the lowered program grows with
    the slice count."""
    plan, arrays = narrow_plan(target_dim)
    sess = ContractionSession(plan, list(arrays), hoist=False)
    sb, s = 4, plan.num_sliced
    nb = -(-sess.n_slices // sb)
    text = sess._all_fn(sb).lower(
        list(arrays), [],
        jax.ShapeDtypeStruct((nb, sb, s), jnp.int32),
        jax.ShapeDtypeStruct((nb, sb), jnp.bool_),
    ).as_text()
    main = next(ln for ln in text.splitlines() if "func public @main" in ln)
    assert f"tensor<{nb}x{sb}x{s}xi32>" in main
    literals = re.findall(r"stablehlo\.constant dense<(.*?)>", text)
    assert sum(map(len, literals)) < sess.n_slices
    # and it sums every slice, as one batch of all ids does
    want = sess.run_slices(np.arange(sess.n_slices))
    assert_close(sess.run_all(sb), want)


def test_sliced_bits_counter_and_ids_put_span():
    plan, arrays = wide_plan()
    prev = trace.enabled()
    trace.set_enabled(True)
    trace.reset()
    metrics.reset()
    try:
        sess = ContractionSession(plan, list(arrays), hoist=True)
        jax.block_until_ready(sess.run_slices(WIDE_IDS[:2]))
        gauge = metrics.snapshot()["gauges"]["engine.sliced_bits"]
        spans = {s.name: s for s in trace.get_spans()}
    finally:
        trace.set_enabled(prev)
        trace.reset()
        metrics.reset()
    assert gauge == plan.num_sliced
    assert spans["engine.ids_put"].attrs["bits"] == plan.num_sliced
    first = spans["engine.run_slices"].attrs["first_id"]
    assert type(first) is int and first == WIDE_IDS[0]


SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import numpy as np
sys.path.insert(0, "tests")
import test_slice_ids as t
from repro.engine.session import ContractionSession
from repro.launch.mesh import make_host_mesh

plan, arrays = t.wide_plan()
mesh = make_host_mesh((4,), ("data",))
out = {}
for hoist in (False, True):
    sess = ContractionSession(plan, list(arrays), hoist=hoist)
    v = sess.run_sharded(mesh, ("data",), slice_batch=1,
                         slice_ids=t.WIDE_IDS)
    v = np.asarray(v, complex).ravel()
    out[str(hoist)] = [v.real.tolist(), v.imag.tolist()]
print("RESULT " + json.dumps(out))
"""


def test_run_sharded_past_2_31_on_four_devices():
    """Six wide ids over four host devices, one per scan step: padded to
    eight lanes, two of them masked."""
    r = subprocess.run(
        [sys.executable, "-c", SHARDED], capture_output=True, text=True,
        timeout=900, **subprocess_kwargs(),
    )
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, r.stdout + "\n" + r.stderr[-3000:]
    got = json.loads(line[0][len("RESULT "):])
    plan, arrays = wide_plan()
    want = sum(reference_slice(plan, arrays, i) for i in WIDE_IDS)
    for hoist in ("False", "True"):
        re, im = got[hoist]
        assert_close((np.array(re) + 1j * np.array(im)).reshape(want.shape),
                     want)
