"""JAX execution of sliced contraction trees.

The planner (pathfinder/slicing/tuning/merging) emits a contraction tree
plus a slicing bitmask ``S``; this module compiles that into a jitted JAX
program:

  * each of the ``2^|S|`` subtasks fixes the sliced indices to one bit
    assignment (``lax.index_in_dim`` on the leaf arrays — shape-stable, so
    a single jitted function serves every subtask); the host hands the
    program each slice id as its row of bits
    (:meth:`ContractionPlan.slice_bits`), so an id may be wider than the
    device's 32-bit integers,
  * subtasks are batched with ``vmap`` (beyond-paper: batching slices
    recovers GEMM efficiency lost to narrow stems — the M dimension grows
    by the slice-batch factor); a ragged final batch is padded with
    wrapped-around slice ids masked out by a validity weight, so any
    ``slice_batch`` works,
  * results are summed — the paper's single all-reduce.

**Two-phase (hoisted) execution.**  The paper's Eq. 4 localizes slicing
overhead to the contractions whose lifetime-closure touches a sliced
index; every other node computes the identical tensor in all ``2^|S|``
subtasks.  :mod:`repro.lowering.partition` splits the tree accordingly
and the plan executes it as a *prologue/epilogue pair*: the
slice-invariant prologue runs **once per plan** on the full leaf arrays
(its outputs — the maximal invariant subtree roots — are materialized
and LRU-cached by leaf fingerprint), and only the slice-dependent
epilogue runs (and is vmapped) inside the slice loop, consuming the
hoisted buffers as captured constants.  ``REPRO_HOIST=0`` (or
``hoist=False``) is the off-switch back to the naive full-tree-per-slice
path; both modes are exact and agree to numerical precision.

Open output indices are first-class: when the network declares
``open_inds`` (e.g. a subset of final qubit wires held open for batched
correlated-amplitude sampling), every slice contributes a *tensor* of
amplitudes — one axis per open index, axes in ``tn.open_inds`` order —
and the cross-slice sum accumulates that whole batch.  One sliced
contraction therefore produces ``2^k`` correlated amplitudes instead of
one, which is the paper's flagship sampling workload (Sec. VI: 1M
correlated samples of Sycamore).  See :mod:`repro.sampling` for the
sampling layer built on top.

Two execution backends share the slice machinery and the lane-dense
layout (:mod:`repro.lowering.layout`: every buffer flat in a static
index order, so a TPU does not pad one-axis-per-index tensors 64×).
The default ``einsum`` oracle path runs every tree node as one XLA
``dot_general``; ``backend="gemm"`` compiles the tree through
:mod:`repro.lowering` into an explicit kernel schedule — each node
normalized to transpose→reshape→GEMM form and refined onto the Pallas
``tiled_matmul`` or XLA's dot per the adaptive tile refiner.  The
schedule is static per plan, so it runs identically under the per-slice
path, the vmapped slice batch, and ``shard_map``.

Every program names its parts with ``jax.named_scope``, so each device
op's ``op_name`` metadata says what it does: ``step<k>.<backend>`` around
step ``k`` of :attr:`ContractionPlan.steps` (``backend`` the refiner's
``pallas``, ``dot`` or ``einsum``), with ``permute`` and ``gemm`` inside
it (:func:`repro.lowering.gemm_form.contract_flat`); ``leaves`` (the
slicing of the leaf arrays), ``output`` (the root into ``out_inds``
order), ``prologue`` (the hoisted program) and ``batch_sum`` (the engine's
masked sum over a slice batch).  Scopes change only metadata: the
optimized program is the same without them.

Distribution across devices lives in :mod:`repro.core.distributed`.
"""

from __future__ import annotations

import dataclasses
import os
import string
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics, trace as _trace
from .contraction_tree import ContractionTree
from .tensor_network import TensorNetwork, bits

_LETTERS = string.ascii_letters

BACKENDS = ("einsum", "gemm")

#: Slice ids are int64 on the host (:meth:`ContractionPlan.slice_bits`),
#: so a plan slices at most this many indices.
MAX_SLICE_BITS = 62


def default_backend() -> str:
    """Execution backend when none is requested: the ``REPRO_BACKEND``
    environment variable (CI runs the tier-1 gate under both values) or
    the einsum oracle path."""
    backend = os.environ.get("REPRO_BACKEND", "einsum")
    if backend not in BACKENDS:
        raise ValueError(
            f"REPRO_BACKEND={backend!r} not in {BACKENDS}"
        )
    return backend


def default_hoist() -> bool:
    """Whether two-phase (slice-invariant hoisted) execution is enabled
    when no explicit ``hoist=`` is requested: the ``REPRO_HOIST``
    environment variable (CI runs the tier-1 gate under both values),
    defaulting to on.  ``REPRO_HOIST=0`` is the documented off-switch
    back to the naive full-tree-per-slice executor."""
    v = os.environ.get("REPRO_HOIST", "1")
    if v not in ("0", "1"):
        raise ValueError(f"REPRO_HOIST={v!r} not in ('0', '1')")
    return v == "1"


def pair_contract_inds(
    inds_a: Sequence, inds_b: Sequence, open_inds: frozenset
) -> tuple[tuple, tuple]:
    """(contracted, out) index tuples for a pairwise contraction, with the
    deterministic ordering convention shared by planner and executor."""
    sa, sb = set(inds_a), set(inds_b)
    contracted = tuple(
        ix for ix in inds_a if ix in sb and ix not in open_inds
    )
    out = tuple(ix for ix in inds_a if ix not in contracted) + tuple(
        ix for ix in inds_b if ix not in contracted and ix not in sa
    )
    return contracted, out


def einsum_expr(inds_a, inds_b, inds_out) -> str:
    local: dict = {}

    def lab(ix):
        if ix not in local:
            local[ix] = _LETTERS[len(local)]
        return local[ix]

    return (
        "".join(lab(i) for i in inds_a)
        + ","
        + "".join(lab(i) for i in inds_b)
        + "->"
        + "".join(lab(i) for i in inds_out)
    )


def simplify_network(
    tn: TensorNetwork, arrays: list[np.ndarray]
) -> tuple[TensorNetwork, list[np.ndarray]]:
    """Absorb rank-1/2 tensors into neighbours (gate fusion), keeping the
    arrays in sync — the Cotengra-style pre-processing the paper applies
    before planning."""
    open_set = frozenset(tn.open_inds)
    inputs = [list(t) for t in tn.inputs]
    arrs = [np.asarray(a) for a in arrays]
    alive = [True] * len(inputs)
    changed = True
    while changed:
        changed = False
        by_ind: dict = {}
        for i, t in enumerate(inputs):
            if alive[i]:
                for ix in t:
                    by_ind.setdefault(ix, []).append(i)
        for i, t in enumerate(inputs):
            if not alive[i] or len(t) > 2:
                continue
            closed = [ix for ix in t if ix not in open_set]
            if not closed:
                continue
            partners = [j for j in by_ind.get(closed[0], []) if j != i and alive[j]]
            if not partners:
                continue
            j = partners[0]
            _, out = pair_contract_inds(inputs[j], t, open_set)
            expr = einsum_expr(inputs[j], t, out)
            arrs[j] = np.einsum(expr, arrs[j], arrs[i])
            inputs[j] = list(out)
            alive[i] = False
            changed = True
            break
    new_inputs = [t for i, t in enumerate(inputs) if alive[i]]
    new_arrays = [a for i, a in enumerate(arrs) if alive[i]]
    return TensorNetwork(new_inputs, tn.open_inds, tn.ind_sizes), new_arrays


def auto_slice_batch(requested: int, n_slices: int) -> int:
    """Clamp the requested slice batch to the slice count.

    Historically this silently shrank to the largest power of two
    dividing ``n_slices`` because ``contract_all`` required exact tiling;
    the executor now pads the final ragged batch (masked by a validity
    weight), so any batch size works and the request is honored as-is."""
    return max(1, min(requested, n_slices))


@dataclasses.dataclass
class _Step:
    lhs: int  # env key
    rhs: int
    out: int
    expr: str
    inds_lhs: tuple = ()
    inds_rhs: tuple = ()
    inds_out: tuple = ()


class ContractionPlan:
    """Compiled sliced-contraction program for one (tree, S) pair.

    ``backend="gemm"`` additionally lowers every step through
    :mod:`repro.lowering` into a refined kernel schedule (``self.
    schedule``); ``backend=None`` resolves via :func:`default_backend`.
    ``dtype`` only informs the refiner's cost model / backend choice —
    execution adapts to the concrete arrays it is handed.

    ``precision`` (``None`` → :func:`~repro.lowering.precision.
    default_precision`, i.e. ``REPRO_PRECISION``) selects the
    mixed-precision mode for the lowered schedule: ``"auto"`` demotes
    MXU steps to bf16-input/fp32-accumulate while the forward error
    model's predicted Linear-XEB fidelity loss stays within
    ``fidelity_tol``; ``"bf16"`` forces every eligible step; ``"fp32"``
    (the default) leaves the plan untouched.  Only meaningful for
    ``backend="gemm"``.
    """

    def __init__(
        self,
        tree: ContractionTree,
        smask: int = 0,
        backend: str | None = None,
        dtype=jnp.complex64,
        precision: str | None = None,
        fidelity_tol: float | None = None,
    ):
        self.tree = tree
        tn = tree.tn
        self.tn = tn
        space = tn.space
        self.smask = smask
        self.sliced_bits = list(bits(smask))
        self.num_sliced = len(self.sliced_bits)
        slicepos = {b: i for i, b in enumerate(self.sliced_bits)}
        sliced_labels = {space.labels[b] for b in self.sliced_bits}
        open_set = frozenset(tn.open_inds)

        # leaf slicing specs: (axis, slice position) — applied high-axis
        # first so earlier axes stay valid.
        self.leaf_specs: list[list[tuple[int, int]]] = []
        node_inds: dict[int, tuple] = {}
        for i, inds in enumerate(tn.inputs):
            spec = [
                (ax, slicepos[space.bit(ix)])
                for ax, ix in enumerate(inds)
                if ix in sliced_labels
            ]
            spec.sort(reverse=True)
            self.leaf_specs.append(spec)
            node_inds[i] = tuple(ix for ix in inds if ix not in sliced_labels)

        self.steps: list[_Step] = []
        for v in tree.contract_order():
            l, r = tree.children[v]
            _, out = pair_contract_inds(node_inds[l], node_inds[r], open_set)
            expr = einsum_expr(node_inds[l], node_inds[r], out)
            node_inds[v] = out
            self.steps.append(
                _Step(l, r, v, expr, node_inds[l], node_inds[r], out)
            )
        self.root = tree.root
        raw_out = node_inds[self.root]
        # canonicalize: output axes follow tn.open_inds declaration order
        want = tuple(ix for ix in tn.open_inds if ix in raw_out)
        self.out_inds = want if want else raw_out

        self.backend = backend if backend is not None else default_backend()
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        self.dtype = jnp.dtype(dtype)
        from ..lowering.precision import (  # lazy: avoid cycle
            DEFAULT_FIDELITY_TOL,
            PRECISION_MODES,
            default_precision,
        )

        self.precision_mode = (
            precision if precision is not None else default_precision()
        )
        if self.precision_mode not in PRECISION_MODES:
            raise ValueError(
                f"precision {self.precision_mode!r} not in {PRECISION_MODES}"
            )
        self.fidelity_tol = (
            DEFAULT_FIDELITY_TOL if fidelity_tol is None
            else float(fidelity_tol)
        )
        self.schedule = None
        if self.backend == "gemm":
            from ..lowering import refine_schedule  # lazy: avoid cycle

            self.schedule = refine_schedule(
                [(s.inds_lhs, s.inds_rhs, s.inds_out) for s in self.steps],
                tn.size_of,
                dtype=self.dtype,
            )

        # two-phase partition: slice-invariant prologue steps (run once
        # per plan) vs slice-dependent epilogue steps (run per slice).
        self.partition = None
        self.prologue_idx: tuple[int, ...] = ()
        self.epilogue_idx: tuple[int, ...] = tuple(range(len(self.steps)))
        self.hoisted_nodes: tuple[int, ...] = ()
        self.prologue_leaves: tuple[int, ...] = ()
        self.epilogue_leaves: tuple[int, ...] = tuple(range(tn.num_tensors))
        if self.num_sliced and self.steps:
            from ..lowering.partition import partition_tree  # lazy: cycle

            part = partition_tree(tree, smask)
            pos = {st.out: k for k, st in enumerate(self.steps)}
            self.partition = part
            self.prologue_idx = tuple(pos[v] for v in part.invariant_nodes)
            self.epilogue_idx = tuple(pos[v] for v in part.epilogue_nodes)
            self.hoisted_nodes = part.hoisted_nodes
            self.prologue_leaves = part.prologue_leaves
            self.epilogue_leaves = part.epilogue_leaves
        # mixed-precision assignment: runs after the partition (epilogue
        # steps weigh 2^|S| in the greedy order) and before the memory
        # planning (its byte accounting must see the storage precision
        # the schedule will actually run at)
        self._itemsize_of: dict[int, int] | None = None
        if self.schedule is not None and self.precision_mode != "fp32":
            from ..lowering.precision import (  # lazy: avoid cycle
                assign_precision,
                storage_itemsizes,
            )

            self.schedule = assign_precision(
                self.schedule,
                mode=self.precision_mode,
                fidelity_tol=self.fidelity_tol,
                epilogue_positions=(
                    self.epilogue_idx if self.num_sliced else None
                ),
                n_slices=1 << self.num_sliced,
            )
            if self.schedule.precision_counts().get("bf16"):
                self._itemsize_of = storage_itemsizes(
                    [(s.lhs, s.rhs, s.out) for s in self.steps],
                    self.schedule.specs,
                    self.dtype,
                    tree.emask,
                )
        # lifetime-based buffer plan (lazy)
        self._memory_plan = None
        # lane-dense layout: every buffer is stored flat in a static index
        # order, and each step's operand orders and GEMM orientation are
        # fixed here (see repro.lowering.layout)
        from ..lowering.layout import store_layout  # lazy: avoid cycle

        specs = (
            self.schedule.specs if self.schedule else [None] * len(self.steps)
        )
        layout = store_layout(
            [
                (st.lhs, st.rhs, st.out,
                 spec is not None and spec.backend == "pallas")
                for st, spec in zip(self.steps, specs)
            ],
            node_inds, self.out_inds, tn.size_of, self.dtype.itemsize,
        )
        self.store_order: dict[int, tuple] = layout.store_order
        self.dense_steps = list(layout.dense_steps)
        # modeled HBM bytes of the lane-dense routes, one pass through
        # the tree, for the chosen orders and the greedy ones, and the
        # steps whose output is stored for its consumer
        self.permute_bytes = layout.permute_bytes
        self.permute_bytes_greedy = layout.permute_bytes_greedy
        self.lookahead_orders = layout.lookahead_orders
        # memoized jitted executables (plan-lifetime — a cached plan
        # served twice skips retracing, not just re-planning)
        self._compiled: dict = {}
        # materialized prologue tensors, LRU-keyed by the fingerprint of
        # the leaf arrays the prologue consumes (cross-call reuse, e.g.
        # repeated sampler calls on one open-qubit batch network)
        from ..lowering.cache import HoistCache  # lazy: avoid cycle

        hoist_bytes = os.environ.get("REPRO_HOIST_CACHE_BYTES", "")
        self._hoist_cache = HoistCache(
            maxsize=int(os.environ.get("REPRO_HOIST_CACHE_SIZE", "8")),
            max_bytes=int(hoist_bytes) if hoist_bytes else None,
        )

    # ------------------------------------------------------------------
    @property
    def num_open(self) -> int:
        """Number of open output indices carried through the stem."""
        return len(self.out_inds)

    @property
    def batch_size(self) -> int:
        """Correlated amplitudes produced per full contraction (2^k)."""
        n = 1
        for ix in self.out_inds:
            n *= self.tn.size_of(ix)
        return n

    def out_shape(self) -> tuple[int, ...]:
        """Shape of the contraction output (one axis per open index)."""
        return tuple(self.tn.size_of(ix) for ix in self.out_inds)

    # ------------------------------------------------------------------
    # two-phase (hoisted) execution metrics
    # ------------------------------------------------------------------
    @property
    def can_hoist(self) -> bool:
        """True when the partition found slice-invariant contractions to
        hoist out of the slice loop."""
        return bool(self.prologue_idx)

    @property
    def invariant_fraction(self) -> float:
        """Fraction of the dense tree cost C(B) that is slice-invariant."""
        return self.partition.invariant_fraction if self.partition else 0.0

    def executed_overhead(self, hoist: bool = True) -> float:
        """Executed-FLOPs overhead over the dense C(B) for the chosen
        execution mode: Eq. 4 for the naive full-tree-per-slice path, the
        prologue + 2^|S|·epilogue cost under hoisting."""
        if self.num_sliced == 0:
            return 1.0
        if hoist and self.partition is not None and self.can_hoist:
            return self.partition.hoisted_overhead()
        return self.tree.slicing_overhead(self.smask)

    def executed_flops(
        self, n_slices: int | None = None, hoist: bool = True
    ) -> float:
        """FLOPs actually executed when contracting ``n_slices`` subtasks
        (default: all ``2^|S|``) under the chosen mode — the quantity the
        obs layer accumulates into ``exec.flops_executed``.  Hoisted:
        one prologue plus ``n`` epilogues; naive: ``n`` full subtasks."""
        total = 1 << self.num_sliced
        n = total if n_slices is None else n_slices
        if hoist and self.partition is not None and self.can_hoist:
            p = self.partition
            return p.invariant_cost + p.per_slice_cost * n
        return self.tree.sliced_cost(self.smask) / total * n

    def hoist_summary(self) -> str:
        """One-line two-phase summary for examples/benchmarks."""
        return (
            f"hoist: inv_frac={self.invariant_fraction:.2f} "
            f"slices={1 << self.num_sliced} "
            f"hoisted_buffers={len(self.hoisted_nodes)} "
            f"overhead naive={self.executed_overhead(False):.3f} -> "
            f"hoisted={self.executed_overhead(True):.3f}"
        )

    # ------------------------------------------------------------------
    # lifetime-based buffer plan
    # ------------------------------------------------------------------
    def memory_plan(self):
        """The lifetime-based :class:`~repro.lowering.memory.MemoryPlan`
        for this plan's ``(tree, S)`` pair — exact live-set peaks per
        execution segment, linear-scan buffer slots, and the per-step
        free schedule :meth:`_run_steps` executes.  Built lazily once per
        plan (pure planner algebra, no arrays touched)."""
        if self._memory_plan is None:
            from ..lowering.memory import plan_memory  # lazy: avoid cycle

            self._memory_plan = plan_memory(
                self.tree, self.smask, itemsize=self.dtype.itemsize,
                part=self.partition, itemsize_of=self._itemsize_of,
            )
        return self._memory_plan

    # ------------------------------------------------------------------
    def slice_bits(self, slice_ids) -> np.ndarray:
        """Slice ids as the device takes them: bit ``j`` of each id,
        ``(id >> j) & 1``, in column ``j``, one int32 column per sliced
        index in :attr:`sliced_bits` order.

        ``slice_ids`` is an int or a sequence of ints (Python or NumPy)
        below ``2**MAX_SLICE_BITS``; the result has shape
        ``np.shape(slice_ids) + (num_sliced,)``.  Ids stay int64 on the
        host and never reach the device whole, so they may be wider than
        its 32-bit integers."""
        if self.num_sliced > MAX_SLICE_BITS:
            raise ValueError(
                f"{self.num_sliced} sliced indices; slice ids hold at most "
                f"{MAX_SLICE_BITS} bits"
            )
        ids = np.asarray(slice_ids, dtype=np.int64)
        shifts = np.arange(self.num_sliced, dtype=np.int64)
        return ((ids[..., None] >> shifts) & 1).astype(np.int32)

    def _run_steps(self, env: dict, step_ids, segment: str = "naive") -> None:
        """Execute the given step positions over ``env`` (shared by the
        prologue, the epilogue, and the naive full-tree path).

        ``env`` holds flat buffers in :attr:`store_order`; each step runs
        through :func:`repro.lowering.gemm_form.contract_flat` on its
        :class:`~repro.lowering.layout.DenseStep`.  Frees are driven by
        the lifetime-based memory plan's per-step free schedule for
        ``segment`` — deterministic last-use drops (in the epilogue this
        keeps the pinned hoisted buffers out of the free lists; they are
        cross-slice captures whose storage is never reclaimable inside
        one subtask)."""
        from ..lowering import gemm_form  # lazy: avoid cycle

        seg = self.memory_plan().segment_for(segment)
        frees = seg.frees if seg is not None else None
        for k in step_ids:
            st = self.steps[k]
            spec = self.schedule.specs[k] if self.schedule else None
            backend = spec.backend if spec is not None else "einsum"
            with jax.named_scope(f"step{k}.{backend}"):
                env[st.out] = gemm_form.contract_flat(
                    spec, self.dense_steps[k], env[st.lhs], env[st.rhs],
                )
            dead = (
                frees[st.out]
                if frees is not None
                else (st.lhs, st.rhs)
            )
            for u in dead:
                del env[u]

    def contract_slice(
        self, arrays: Sequence[jnp.ndarray], slice_bits, hoisted=None
    ):
        """Contract one subtask: ``slice_bits`` is its row of
        :meth:`slice_bits`, the value of each sliced index.

        ``hoisted`` (from :meth:`contract_prologue`) seeds the environment
        with the materialized slice-invariant buffers, so only the
        epilogue steps run; ``None`` executes the full tree (naive)."""
        env: dict[int, jnp.ndarray] = {}
        if hoisted is None:
            leaf_ids: Sequence[int] = range(len(arrays))
            step_ids: Sequence[int] = range(len(self.steps))
            segment = "naive"
        else:
            env.update(zip(self.hoisted_nodes, hoisted))
            leaf_ids = self.epilogue_leaves
            step_ids = self.epilogue_idx
            segment = "epilogue"
        with jax.named_scope("leaves"):
            for i in leaf_ids:
                a = jnp.asarray(arrays[i])
                for axis, spos in self.leaf_specs[i]:
                    a = jax.lax.dynamic_index_in_dim(
                        a, slice_bits[spos], axis=axis, keepdims=False
                    )
                env[i] = a.reshape(-1)
        self._run_steps(env, step_ids, segment)
        return self._output(env[self.root])

    def _output(self, flat):
        """The root buffer, from its storage order into ``out_inds``
        order and shape (one axis per open index)."""
        from ..lowering.layout import permute_flat  # lazy: avoid cycle

        with jax.named_scope("output"):
            out = permute_flat(
                flat, self.store_order[self.root], self.out_inds,
                self.tn.size_of,
            )
            return out.reshape(self.out_shape())

    # ------------------------------------------------------------------
    def _prologue_outputs(self, arrays) -> list[jnp.ndarray]:
        """Run the slice-invariant prologue on the full (unsliced) leaf
        arrays and return the hoisted frontier buffers in
        ``hoisted_nodes`` order.  Invariant leaves carry no sliced index
        by construction, so no slice specs apply here."""
        with jax.named_scope("prologue"):
            env: dict[int, jnp.ndarray] = {
                i: jnp.asarray(arrays[i]).reshape(-1)
                for i in self.prologue_leaves
            }
            self._run_steps(env, self.prologue_idx, "prologue")
            return [env[v] for v in self.hoisted_nodes]

    def contract_prologue(
        self, arrays, use_cache: bool = True, cache_key=None
    ):
        """Materialize the slice-invariant prologue once.

        The result is memoized two ways: the jitted program on the plan
        (no retracing), and the concrete output buffers in an LRU keyed
        by :func:`repro.lowering.cache.leaf_key` over the prologue's
        leaf arrays.  Device-resident leaves are keyed by shape/dtype +
        buffer identity — no device→host transfer on the hot path; the
        key's keep-alive references ride with the cache entry so an id
        can never be recycled while its entry is live.  Host (numpy)
        leaves fall back to value hashing.  ``cache_key``, a
        ``leaf_key(leaves, self.prologue_leaves)`` taken earlier from
        leaves equal to ``arrays`` (a session takes it from its host
        leaves when it opens), is used in place of keying ``arrays``
        again.  Set ``REPRO_HOIST_CACHE_SIZE=0`` or ``use_cache=False``
        to skip both the key and the cache.
        """
        if not self.can_hoist:
            return []

        def compute():
            ck = ("prologue",)
            fn = self._compiled.get(ck) or self._compiled.setdefault(
                ck, jax.jit(lambda a: self._prologue_outputs(a))
            )
            with _trace.span(
                "exec.prologue", cat="exec", buffers=len(self.hoisted_nodes)
            ):
                out = fn(list(arrays))
                _trace.sync(out)
            _metrics.inc(
                "exec.flops_executed", self.partition.invariant_cost
            )
            return out

        if use_cache and self._hoist_cache.maxsize > 0:
            if cache_key is None:
                cache_key = self._leaf_key(arrays)
            key, keepalive = cache_key
            # single-flight: concurrent sessions over the same leaves
            # (serving tenants on one family) materialize the prologue
            # once — the waiters get the leader's buffers, and the
            # invariant-cost FLOPs are counted exactly once.
            # third slot: per-Mesh replicated device-put copies, filled
            # lazily by contract_prologue_replicated on the sharded path
            return self._hoist_cache.single_flight(
                key, lambda: (compute(), keepalive, {})
            )[0]
        return compute()

    def _leaf_key(self, arrays) -> tuple[str, tuple]:
        from ..lowering.cache import leaf_key  # lazy: cycle

        return leaf_key(arrays, self.prologue_leaves)

    def contract_prologue_replicated(
        self, arrays, mesh, use_cache: bool = True, cache_key=None
    ):
        """Prologue buffers device-put replicated over ``mesh`` — the
        form ``contract_sharded`` captures into its shard_map worker.

        The placed copies are cached *in the same HoistCache entry* as
        the host-side prologue outputs, keyed by ``mesh``: repeated
        sharded calls on a plan-cache hit reuse the already-broadcast
        buffers instead of re-issuing the device_put every invocation
        (``exec.hoist_replicated_reuse`` counts the skips,
        ``exec.hoist_replicated_put`` the actual broadcasts).
        ``cache_key`` is as in :meth:`contract_prologue`."""
        if not self.can_hoist:
            return []
        cached = use_cache and self._hoist_cache.maxsize > 0
        if cached and cache_key is None:
            cache_key = self._leaf_key(arrays)
        out = self.contract_prologue(
            arrays, use_cache=use_cache, cache_key=cache_key
        )
        entry = key = None
        if cached:
            key = cache_key[0]
            entry = self._hoist_cache.get(key)
            if entry is not None and len(entry) > 2:
                placed = entry[2].get(mesh)
                if placed is not None:
                    _metrics.inc("exec.hoist_replicated_reuse")
                    return placed
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(mesh, PartitionSpec())
        placed = [jax.device_put(o, sharding) for o in out]
        _metrics.inc("exec.hoist_replicated_put")
        if entry is not None and len(entry) > 2:
            entry[2][mesh] = placed
            # re-put so the cache's byte accounting sees the new copies
            self._hoist_cache.put(key, entry)
        return placed

    # ------------------------------------------------------------------
    def contract_all(
        self,
        arrays: Sequence[jnp.ndarray],
        slice_batch: int = 8,
        hoist: bool | None = None,
    ) -> jnp.ndarray:
        """Sum over all 2^|S| subtasks (single host) — strategy adapter
        over the unified engine: a one-shot
        :class:`~repro.engine.session.ContractionSession` running the
        scan-of-vmapped-batches strategy (:meth:`~repro.engine.session.
        ContractionSession.run_all`).  ``hoist`` selects two-phase
        execution (default ``REPRO_HOIST``)."""
        from ..engine.session import ContractionSession  # lazy: cycle

        return ContractionSession(self, arrays, hoist=hoist).run_all(
            slice_batch=slice_batch
        )


def contract_dense(
    tn: TensorNetwork, arrays: Sequence[np.ndarray], tree: ContractionTree
) -> jnp.ndarray:
    """Unsliced contraction (reference path)."""
    return ContractionPlan(tree, 0).contract_all(arrays)
