"""Mixed-precision contraction under an XEB error budget.

Gates the PR-9 stack:

  1. the forward error model + greedy demotion (``repro.lowering.
     precision``): monotone in the fidelity tolerance, zero-tolerance
     reproduces the fp32 plan *bitwise*;
  2. statevector-oracle conformance: auto plans stay within the
     requested Linear-XEB tolerance end-to-end, across hoist modes and
     the shard_map sampling path;
  3. the pinned syc-12 regression gate (CI ``-k xeb_gate``): modeled
     epilogue speedup >= 1.3x, total HBM traffic strictly lower, |S|
     never larger, measured amplitude error within tolerance;
  4. plan-cache fingerprints: the resolved precision mode always joins
     the key, the tolerance only off fp32;
  5. bf16 kernel parity: the lane-dense Pallas step is bitwise against
     a plain permute + kernel reference at bf16, and the per-op bf16
     paths stay within the bf16 forward-error envelope of fp32.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.api import plan_compiled, sample_bitstrings, simulate_amplitude
from repro.core.executor import ContractionPlan, simplify_network
from repro.core.tensor_network import popcount
from repro.lowering import (
    DEFAULT_FIDELITY_TOL,
    assign_precision,
    default_precision,
    node_amp_error,
    refine_tree_schedule,
    tree_storage_itemsizes,
)
from repro.lowering.precision import predicted_fidelity_loss
from repro.quantum import statevector
from repro.quantum.circuits import (
    circuit_to_network,
    random_1d_circuit,
    sycamore_like,
)
from repro.quantum.xeb import xeb_from_amplitudes

SYC_TD = 18  # pinned syc-12 planner config (matches bench_end_to_end)
GATE_TOL = 0.05  # the "realistic" XEB budget the gate certifies at


@pytest.fixture(scope="module")
def syc():
    circ = sycamore_like(4, 5, 12, seed=0)
    tn, arrays = circuit_to_network(circ, bitstring="0" * circ.num_qubits)
    tn, arrays = simplify_network(tn, arrays)
    return circ, tn, arrays


@pytest.fixture(scope="module")
def syc_oracle(syc):
    circ, _, _ = syc
    return complex(statevector.amplitude(circ, "0" * circ.num_qubits))


@pytest.fixture(scope="module")
def syc_fp32(syc):
    """(plan, report, amplitude) of the pinned fp32 baseline."""
    _, tn, arrays = syc
    plan, report = plan_compiled(
        tn, SYC_TD, backend="gemm", use_cache=False,
        slicing_mode="peak", precision="fp32",
    )
    amp = complex(np.asarray(plan.contract_all(arrays, slice_batch=8)))
    return plan, report, amp


@pytest.fixture(scope="module")
def syc_auto(syc):
    """(plan, report, amplitude) of the auto plan at the gate budget."""
    _, tn, arrays = syc
    plan, report = plan_compiled(
        tn, SYC_TD, backend="gemm", use_cache=False,
        slicing_mode="peak", precision="auto", fidelity_tol=GATE_TOL,
    )
    amp = complex(np.asarray(plan.contract_all(arrays, slice_batch=8)))
    return plan, report, amp


# ----------------------------------------------------------------------
# error model + assignment algebra (no execution)
# ----------------------------------------------------------------------
def test_default_precision_env(monkeypatch):
    monkeypatch.delenv("REPRO_PRECISION", raising=False)
    assert default_precision() == "fp32"
    monkeypatch.setenv("REPRO_PRECISION", "auto")
    assert default_precision() == "auto"
    monkeypatch.setenv("REPRO_PRECISION", "fp64")
    with pytest.raises(ValueError):
        default_precision()


def test_error_model_monotone_in_k_and_depth(syc):
    _, tn, _ = syc
    sched = refine_tree_schedule(_tree_of(syc), 0)
    forms = [s.form for s in sched.specs]
    by_k = sorted(forms, key=lambda f: f.K)
    errs = [node_amp_error(f) for f in by_k]
    assert all(e > 0 for e in errs)
    assert errs == sorted(errs)  # grows with K at depth 0
    f = forms[0]
    assert node_amp_error(f, depth=8) > node_amp_error(f, depth=0)


def _tree_of(syc_fixture):
    from repro.optimize import oneshot_plan

    _, tn, _ = syc_fixture
    shot = oneshot_plan(tn, SYC_TD, seed=0, slicing_mode="peak")
    return shot.tree


def test_assignment_monotone_and_certified(syc):
    """bf16 sets are nested as the tolerance grows (strict-prefix
    admission) and every assignment self-certifies within its budget."""
    tree = _tree_of(syc)
    sched = refine_tree_schedule(tree, 0)
    prev: set[int] = set()
    for tol in (0.0, 1e-3, 5e-3, 0.02, 0.05, 0.5):
        out = assign_precision(sched, mode="auto", fidelity_tol=tol)
        cur = {
            i for i, s in enumerate(out.specs) if s.precision == "bf16"
        }
        assert prev <= cur, f"tol={tol} dropped a prior demotion"
        assert predicted_fidelity_loss(out.predicted_amp_error) <= tol
        prev = cur
    assert assign_precision(sched, mode="auto", fidelity_tol=0.0).specs \
        == sched.specs
    forced = assign_precision(sched, mode="bf16", fidelity_tol=1e9)
    assert set(
        i for i, s in enumerate(forced.specs) if s.precision == "bf16"
    ) >= prev


def test_storage_itemsizes_halve_only_bf16_consumers(syc):
    tree = _tree_of(syc)
    iso = tree_storage_itemsizes(tree, 0, mode="bf16", fidelity_tol=1e9)
    assert iso  # the pinned syc-12 schedule has MXU steps to demote
    assert set(iso.values()) <= {4, 8}  # halved or full, nothing else
    assert 4 in iso.values()  # some node is actually stored bf16
    assert tree_storage_itemsizes(tree, 0, mode="fp32") is None


# ----------------------------------------------------------------------
# zero tolerance == fp32, bitwise
# ----------------------------------------------------------------------
def test_tol_zero_bitwise_fp32(syc, syc_fp32):
    _, tn, arrays = syc
    plan32, _, amp32 = syc_fp32
    p0, r0 = plan_compiled(
        tn, SYC_TD, backend="gemm", use_cache=False,
        slicing_mode="peak", precision="auto", fidelity_tol=0.0,
    )
    amp0 = complex(np.asarray(p0.contract_all(arrays, slice_batch=8)))
    assert p0.smask == plan32.smask
    assert p0.schedule.specs == plan32.schedule.specs
    assert (r0.precision_counts or {}).get("bf16", 0) == 0
    assert amp0 == amp32  # bitwise, not allclose


# ----------------------------------------------------------------------
# pinned syc-12 gate (CI: -k xeb_gate)
# ----------------------------------------------------------------------
def test_syc12_xeb_gate(syc_fp32, syc_auto, syc_oracle):
    plan32, rep32, amp32 = syc_fp32
    plana, repa, ampa = syc_auto

    # the fp32 baseline itself is oracle-exact
    assert abs(amp32 - syc_oracle) / abs(syc_oracle) < 1e-3

    # the auto plan demoted something and certified it
    n16 = (repa.precision_counts or {}).get("bf16", 0)
    assert n16 >= 1
    assert repa.precision == "auto" and repa.fidelity_tol == GATE_TOL
    assert predicted_fidelity_loss(repa.predicted_amp_error) <= GATE_TOL

    # |S| never larger under bf16 storage (peak-mode pruning)
    assert plana.num_sliced <= plan32.num_sliced

    # modeled epilogue time: >= 1.3x lower end-to-end
    def epi_total(plan):
        per_slice = sum(
            plan.schedule.specs[k].modeled_time_s for k in plan.epilogue_idx
        )
        return per_slice * (1 << plan.num_sliced)

    assert epi_total(plan32) >= 1.3 * epi_total(plana)

    # total modeled HBM traffic strictly lower
    def hbm_total(plan):
        return plan.schedule.hbm_traffic_bytes() * (1 << plan.num_sliced)

    assert hbm_total(plana) < hbm_total(plan32)

    # measured amplitude error within the XEB budget
    assert abs(ampa - syc_oracle) / abs(syc_oracle) <= GATE_TOL


def test_report_row_mentions_precision(syc_auto):
    _, repa, _ = syc_auto
    row = repa.row()
    assert "prec=auto" in row and "tol=0.05" in row


# ----------------------------------------------------------------------
# execution-mode matrix: hoist on/off + shard_map sampling
# ----------------------------------------------------------------------
@pytest.mark.parametrize("hoist", [False, True])
def test_auto_amplitude_within_tol_hoist_modes(
    syc, syc_auto, syc_oracle, hoist
):
    _, _, arrays = syc
    plana, _, _ = syc_auto
    amp = complex(
        np.asarray(plana.contract_all(arrays, slice_batch=8, hoist=hoist))
    )
    assert abs(amp - syc_oracle) / abs(syc_oracle) <= GATE_TOL


def test_sampling_xeb_within_tolerance_shard_map(syc):
    """Open-batch sampling (the shard_map path, 1-device mesh) agrees
    with its fp32 twin within the budget, amplitude-wise and XEB-wise."""
    from repro.launch.mesh import make_host_mesh

    circ, _, _ = syc
    mesh = make_host_mesh((1,), ("data",))
    kw = dict(
        num_samples=128, open_qubits=(16, 17, 18, 19), target_dim=SYC_TD,
        seed=1, backend="gemm", use_cache=False, slice_batch=4,
        slicing_mode="peak",
    )
    base = sample_bitstrings(circ, precision="fp32", **kw)
    mixed = sample_bitstrings(
        circ, mesh=mesh, axis_names=("data",),
        precision="auto", fidelity_tol=GATE_TOL, **kw,
    )
    a32 = np.asarray(base.batch.amplitudes)
    a16 = np.asarray(mixed.batch.amplitudes)
    scale = np.abs(a32).max()
    assert np.abs(a16 - a32).max() <= GATE_TOL * scale
    x32 = xeb_from_amplitudes(circ.num_qubits, a32.ravel())
    x16 = xeb_from_amplitudes(circ.num_qubits, a16.ravel())
    assert abs(x16 - x32) <= 3 * GATE_TOL * (1.0 + abs(x32))


def test_einsum_backend_precision_inert():
    """precision= is accepted (and inert) on the einsum backend."""
    circ = random_1d_circuit(8, 6, seed=1)
    want = complex(statevector.amplitude(circ, "0" * 8))
    res = simulate_amplitude(
        circ, "0" * 8, target_dim=6, backend="einsum", use_cache=False,
        precision="auto", fidelity_tol=GATE_TOL,
    )
    assert res.plan.schedule is None
    assert res.report.precision_counts is None
    assert abs(complex(res.value) - want) < 1e-5


# ----------------------------------------------------------------------
# plan-cache fingerprints
# ----------------------------------------------------------------------
def test_plan_cache_separates_precision(monkeypatch):
    circ = random_1d_circuit(9, 7, seed=5)
    tn, arrays = circuit_to_network(circ, bitstring="0" * 9)
    tn, arrays = simplify_network(tn, arrays)
    monkeypatch.setenv("REPRO_PRECISION", "fp32")
    p1, r1 = plan_compiled(tn, 7, backend="gemm")
    monkeypatch.setenv("REPRO_PRECISION", "auto")
    p2, r2 = plan_compiled(tn, 7, backend="gemm")
    assert p1 is not p2  # env mode joins the fingerprint
    p3, r3 = plan_compiled(tn, 7, backend="gemm")
    assert p3 is p2 and r3.cache_hit
    monkeypatch.delenv("REPRO_PRECISION")
    # off fp32 the tolerance separates plans ...
    pa, _ = plan_compiled(tn, 7, backend="gemm", precision="auto",
                          fidelity_tol=0.05)
    pb, _ = plan_compiled(tn, 7, backend="gemm", precision="auto",
                          fidelity_tol=0.1)
    pc, rc = plan_compiled(tn, 7, backend="gemm", precision="auto",
                           fidelity_tol=0.05)
    assert pa is not pb
    assert pc is pa and rc.cache_hit
    # ... while fp32 plans ignore it (no cache fragmentation)
    pf1, _ = plan_compiled(tn, 7, backend="gemm", precision="fp32",
                           fidelity_tol=0.05)
    pf2, rf2 = plan_compiled(tn, 7, backend="gemm", precision="fp32",
                             fidelity_tol=0.1)
    assert pf2 is pf1 and rf2.cache_hit
    # the unset mode resolves to fp32 (REPRO_PRECISION unset) and shares
    # that entry at any tolerance
    pd, rd = plan_compiled(tn, 7, backend="gemm", fidelity_tol=0.2)
    assert pd is pf1 and rd.cache_hit


# ----------------------------------------------------------------------
# peak-mode |S| never larger
# ----------------------------------------------------------------------
@pytest.mark.parametrize("td", [16, 18, 20])
def test_peak_mode_slices_never_larger(syc, td):
    from repro.optimize import oneshot_plan

    _, tn, _ = syc
    s32 = oneshot_plan(tn, td, seed=0, slicing_mode="peak",
                       precision="fp32")
    s16 = oneshot_plan(tn, td, seed=0, slicing_mode="peak",
                       precision="auto", fidelity_tol=GATE_TOL)
    assert popcount(s16.smask) <= popcount(s32.smask)
    # prune-only second pass: the bf16 mask is a subset of the fp32 one
    assert s16.smask & ~s32.smask == 0


# ----------------------------------------------------------------------
# calibration splits precision classes
# ----------------------------------------------------------------------
def test_calibrate_precision_classes(syc, syc_auto):
    from repro.obs.calibrate import calibrate_plan

    _, _, arrays = syc
    plana, repa, _ = syc_auto
    rep = calibrate_plan(plana, arrays, slice_id=0, repeat=1)
    assert rep.backend == plana.backend
    classes = rep.ratio_by_class()
    assert classes
    # at least one row runs off full fp32 and is classed separately
    assert any("[" in cls for cls in classes), classes
    for r in rep.rows:
        assert r.precision in ("fp32", "bf16", "mixed")


# ----------------------------------------------------------------------
# kernel parity at bf16
# ----------------------------------------------------------------------
def test_matmul_bf16_within_forward_error():
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    a = rng.standard_normal((256, 192)).astype(np.float32)
    b = rng.standard_normal((192, 128)).astype(np.float32)
    full = np.asarray(ops.matmul(jnp.asarray(a), jnp.asarray(b),
                                 interpret=True))
    demoted = np.asarray(ops.matmul(jnp.asarray(a), jnp.asarray(b),
                                    interpret=True, precision="bf16"))
    want = np.matmul(
        np.asarray(jnp.asarray(a).astype(jnp.bfloat16), dtype=np.float64),
        np.asarray(jnp.asarray(b).astype(jnp.bfloat16), dtype=np.float64),
    )
    scale = np.abs(full).max()
    # demotion really happened, and stayed inside the bf16 envelope
    assert np.abs(demoted - full).max() > 0
    assert np.abs(demoted - want).max() <= 1e-2 * scale
    assert np.abs(demoted - full).max() <= 4 * node_amp_error_bound(192) * scale


def node_amp_error_bound(k: int) -> float:
    """Loose forward bound used by the kernel parity tests: 2u·sqrt(
    1 + log2(K)/8) — the model's depth-0 per-node term."""
    import math

    return 2.0 * 2.0 ** -9 * math.sqrt(1.0 + math.log2(max(k, 1)) / 8.0)


@pytest.mark.parametrize("case", [0, 2, 3])
def test_pallas_step_bitwise_vs_reference_bf16(case):
    """The lane-dense Pallas step at bf16 is *bitwise* the kernel run on
    a plain numpy permutation of the same operands — layout moves data,
    never values — and stays inside the bf16 envelope of einsum."""
    from repro.kernels import ops
    from repro.lowering import dense_step, gemm_form, lower_step
    from repro.lowering.refiner import GemmSpec

    rng = np.random.default_rng(case)
    labels = [f"i{j}" for j in range(9)]
    sizes = {ix: 2 for ix in labels}
    perm = list(rng.permutation(labels))
    inds_a = tuple(perm[:6])
    inds_b = tuple(perm[3:])
    inds_out = tuple(perm[:3]) + tuple(perm[6:])
    cplx = case != 0
    form = lower_step(inds_a, inds_b, inds_out, sizes.__getitem__)
    a = rng.standard_normal((2,) * 6)
    b = rng.standard_normal((2,) * 6)
    if cplx:
        a = a + 1j * rng.standard_normal((2,) * 6)
        b = b + 1j * rng.standard_normal((2,) * 6)
    dt = np.complex64 if cplx else np.float32
    a, b = a.astype(dt), b.astype(dt)
    spec = GemmSpec(form, "pallas", 4, 4, 4, 0.0, 0.0, precision="bf16")
    ds = dense_step(inds_a, inds_b, inds_out, sizes.__getitem__,
                    canonical=True)
    got = np.asarray(gemm_form.contract_flat(
        spec, ds, jnp.asarray(a).reshape(-1), jnp.asarray(b).reshape(-1)
    ))
    a2 = a.transpose([inds_a.index(i) for i in ds.a_gemm])[None]
    b2 = b.transpose([inds_b.index(i) for i in ds.b_gemm])[None]
    ref = np.asarray(ops.matmul(
        jnp.asarray(a2.reshape(ds.a_shape)[0]),
        jnp.asarray(b2.reshape(ds.b_shape)[0]),
        bm=4, bn=4, bk=4, min_kernel_dim=1, interpret=True,
        precision="bf16",
    )).reshape(-1)
    assert np.array_equal(got, ref)
    want = np.einsum(form.expr, a, b).transpose(
        [inds_out.index(i) for i in ds.out_order]
    ).reshape(-1)
    assert np.abs(got - want).max() <= 0.05 * np.abs(want).max()
