"""Unified execution engine: one session layer under every slice driver.

Before this module existed the per-slice dispatch/hoist/mask/metrics
logic was quadruplicated across ``contract_all`` (vmapped scan),
``contract_sharded`` (shard_map + psum), ``contract_resumable``
(per-slice jit calls) and ``contract_multihost`` (scheduler-driven
ranges) — every new capability (telemetry, precision) had to
be threaded through four paths.  A :class:`ContractionSession` is the
single owner of that logic: a compiled
:class:`~repro.core.executor.ContractionPlan` bound to concrete leaf
arrays, with the two-phase hoist mode resolved once and the hoisted
prologue materialized once (through the plan's HoistCache, so sessions
on the same plan + leaves share the buffers across calls *and* across
server tenants).

The primitive is :meth:`ContractionSession.run_slices`: one jitted
masked-vmap batch over explicit slice ids — the unit the multi-host
scheduler claims, the unit the serving engine dispatches, and the unit
the scan/shard_map strategies iterate.  Everything a strategy needs
beyond it is shared here exactly once:

  * :func:`mask_invalid` — the ragged-batch validity select
    (``jnp.where``, never a weight multiply: ``0 * NaN`` leaks),
  * :func:`padded_ids` — wrapped-around slice-id padding to a chunk
    multiple,
  * :func:`record_execution` — the executed/padded/FLOPs work
    accounting,
  * jit memoization on the plan's ``_compiled`` dict (all sessions on a
    cached plan share traced programs),
  * per-step free schedules and lane-dense step dispatch (via
    ``plan.contract_slice`` → ``_run_steps`` — already single-sited).

The four public drivers are thin strategy adapters over this class; the
serving layer (:mod:`repro.engine.server`) builds directly on it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics, trace as _trace


def mask_invalid(contrib: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Zero the padded lanes of a leading batch axis.

    ``valid`` is a boolean vector over ``contrib``'s leading axis.  The
    mask is a select, NOT a weight multiply: a NaN/Inf in a padded
    contribution would leak through ``0 * NaN == NaN`` (a legitimately
    overflowing slice would corrupt the whole sum), and a float32 weight
    multiply is dtype-lossy under x64."""
    return jnp.where(
        valid.reshape((-1,) + (1,) * (contrib.ndim - 1)),
        contrib,
        jnp.zeros((), contrib.dtype),
    )


def padded_ids(
    n_slices: int, multiple: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """Slice ids padded (by wrap-around) to a multiple of ``multiple``.

    Returns ``(ids, valid, total)``: int64 ids of length ``total`` (the
    ceiling multiple), a boolean validity vector marking the real ids,
    and ``total`` itself.  Padding with *wrapped* ids keeps every lane a
    legal slice id (shape-stable indexing); the validity mask is what
    keeps the duplicates out of the sum."""
    total = -(-n_slices // multiple) * multiple
    ids = np.arange(total, dtype=np.int64) % n_slices
    valid = np.arange(total) < n_slices
    return ids, valid, total


def record_execution(plan, executed: int, padded: int, hoist: bool) -> None:
    """Work accounting shared by every strategy adapter.

    ``executed`` counts *real* slice ids summed into the amplitude;
    ``padded`` counts masked lanes (wrapped-around ids whose contribution
    a validity select zeroes out).  The two are disjoint by contract —
    inflating ``exec.slices_executed`` with padded lanes historically
    made multi-host FLOPs accounting drift from the single-host
    scan's on the same plan.  Prologue FLOPs are counted where the
    prologue actually runs (``contract_prologue`` — a hoist-cache hit
    executes nothing), so only the per-slice epilogue cost lands here
    under hoisting."""
    _metrics.inc("exec.slices_executed", executed)
    if padded:
        _metrics.inc("exec.padded_slices", padded)
    if hoist:
        _metrics.inc(
            "exec.flops_executed", plan.partition.per_slice_cost * executed
        )
    else:
        _metrics.inc(
            "exec.flops_executed", plan.executed_flops(executed, hoist=False)
        )


def put_leaves(arrays: list, sharding=None) -> list:
    """Leaf arrays on the device, as one ``jax.device_put`` of the list
    (on the default device, uncommitted, when ``sharding`` is None).

    Traced as ``engine.leaves_put``; counts the arrays in
    ``engine.leaf_puts`` and their bytes in ``engine.leaf_put_bytes``."""
    with _trace.span("engine.leaves_put", cat="engine", leaves=len(arrays)):
        placed = jax.device_put(arrays, sharding)
        _trace.sync(placed)
    _metrics.inc("engine.leaf_puts", len(placed))
    _metrics.inc("engine.leaf_put_bytes", sum(int(a.nbytes) for a in placed))
    return placed


class ContractionSession:
    """A compiled plan bound to leaf arrays, ready to execute slices.

    The session resolves the execution-time choices once — two-phase
    hoist mode (``hoist``, default ``REPRO_HOIST``, silently off when
    the plan has nothing to hoist) — and materializes the slice-invariant
    prologue lazily on first use, through the plan's leaf-keyed
    HoistCache so repeated sessions over the same leaves (sampler calls,
    serving tenants) skip it entirely.

    The leaves go to the device once, when the session opens
    (:func:`put_leaves`; ``arrays`` holds the device copies), so a call
    sends only its ids' bits and the validity mask.  The prologue's
    HoistCache key is taken from the host leaves at the same time: by
    value, as the device copies would key by identity only.

    Strategies:

      * :meth:`run_slice` — one subtask, one jit call (the resumable
        driver's unit),
      * :meth:`run_slices` — THE primitive: one jitted masked-vmap batch
        over explicit ids (the multi-host scheduler's and the serving
        engine's unit),
      * :meth:`run_all` — all ``2^|S|`` subtasks as a scan of vmapped
        batches (single host),
      * :meth:`run_sharded` — slice ids sharded over a mesh via
        shard_map, one psum.

    All jitted programs are memoized on ``plan._compiled`` (keyed by
    strategy + hoist mode), so every session on a plan-cache hit reuses
    the traced executables; concurrent sessions converge on one program
    via ``setdefault``.
    """

    def __init__(self, plan, arrays, hoist: bool | None = None):
        from ..core.executor import default_hoist  # lazy: avoid cycle

        self.plan = plan
        host = list(arrays)
        h = default_hoist() if hoist is None else bool(hoist)
        self.hoist = bool(h and plan.can_hoist)
        self._prologue_key = plan._leaf_key(host) if self.hoist else None
        self.arrays = put_leaves(host)
        self._hoisted: list | None = None
        self._replicated: dict = {}  # mesh -> leaves replicated over it
        _metrics.set_gauge("engine.sliced_bits", plan.num_sliced)
        _metrics.set_gauge("plan.permute_bytes", plan.permute_bytes)
        _metrics.set_gauge("plan.permute_bytes_greedy",
                           plan.permute_bytes_greedy)
        _metrics.set_gauge("plan.lookahead_orders", plan.lookahead_orders)

    # ------------------------------------------------------------------
    @property
    def n_slices(self) -> int:
        return 1 << self.plan.num_sliced

    def hoisted(self) -> list:
        """The materialized slice-invariant prologue buffers (``[]``
        when hoisting is off) — computed once per session, served from
        the plan's HoistCache across sessions on the same leaves."""
        if not self.hoist:
            return []
        if self._hoisted is None:
            self._hoisted = self.plan.contract_prologue(
                self.arrays, cache_key=self._prologue_key
            )
        return self._hoisted

    def hoisted_replicated(self, mesh) -> list:
        """Prologue buffers device-put replicated over ``mesh`` (the
        form the shard_map strategy captures); cached per (leaves, mesh)
        in the same HoistCache entry as the host-side outputs."""
        if not self.hoist:
            return []
        return self.plan.contract_prologue_replicated(
            self.arrays, mesh, cache_key=self._prologue_key
        )

    def leaves_replicated(self, mesh) -> list:
        """The leaves device-put replicated over ``mesh``, once per
        (session, mesh)."""
        placed = self._replicated.get(mesh)
        if placed is None:
            from jax.sharding import NamedSharding, PartitionSpec

            placed = self._replicated.setdefault(
                mesh,
                put_leaves(self.arrays, NamedSharding(mesh, PartitionSpec())),
            )
        return placed

    def out_struct(self):
        """``jax.ShapeDtypeStruct`` of one subtask's output (and of the
        final amplitude) — memoized on the plan: every session over one
        plan shares the same network shapes."""
        plan = self.plan
        key = ("out_struct",)
        s = plan._compiled.get(key)
        if s is None:
            s = plan._compiled.setdefault(
                key,
                jax.eval_shape(
                    lambda: plan.contract_slice(
                        list(self.arrays), plan.slice_bits(0)
                    )
                ),
            )
        return s

    def zeros(self) -> np.ndarray:
        """A host-side zero accumulator of the output shape/dtype."""
        s = self.out_struct()
        return np.zeros(s.shape, s.dtype)

    # ------------------------------------------------------------------
    # strategy: one subtask per jit call (resumable driver's unit)
    # ------------------------------------------------------------------
    def run_slice(self, slice_id) -> jnp.ndarray:
        """Contract one subtask as an independent jit call."""
        plan, hoist = self.plan, self.hoist
        ck = ("sess_slice", hoist)
        fn = plan._compiled.get(ck) or plan._compiled.setdefault(
            ck,
            jax.jit(
                lambda arrs, hbufs, sbits: plan.contract_slice(
                    arrs, sbits, hbufs if hoist else None
                )
            ),
        )
        return fn(
            list(self.arrays), list(self.hoisted()),
            jnp.asarray(plan.slice_bits(slice_id)),
        )

    # ------------------------------------------------------------------
    # THE primitive: one jitted masked-vmap batch over explicit ids
    # ------------------------------------------------------------------
    def run_slices(self, slice_ids, valid=None) -> jnp.ndarray:
        """Execute a batch of slice ids and return the masked partial sum.

        ``slice_ids`` (ints below ``2**MAX_SLICE_BITS``) may contain
        wrapped-around padding ids; ``valid`` (default all-true) marks
        the lanes that contribute.  The device program takes the ids as
        a ``(batch, |S|)`` array of their bits (``plan.slice_bits``).
        One jitted program serves every batch size (jit re-specializes
        per shape and caches internally); the masking select and the
        vmapped ``contract_slice`` dispatch — free schedules, layouts,
        precision — are the single shared implementation.

        Traced as ``engine.run_slices``, with two children:
        ``engine.ids_put`` (the ids' bits and the mask onto the device;
        attribute ``bits`` = |S|) and
        ``engine.launch`` (the jitted call up to its return, not to the
        device's finish)."""
        ids = np.asarray(slice_ids, dtype=np.int64)
        if valid is None:
            valid = np.ones(ids.shape, dtype=bool)
        fn, hbufs = self._batch_fn(), list(self.hoisted())
        with _trace.span(
            "engine.run_slices", cat="engine", ids=int(ids.size),
            first_id=int(ids[0]) if ids.size else None,
        ):
            bits_d, valid_d = self._put_ids(ids, valid)
            with _trace.span("engine.launch", cat="engine"):
                return fn(list(self.arrays), hbufs, bits_d, valid_d)

    def _put_ids(self, ids: np.ndarray, valid):
        """The ids' bits and the validity mask on the device."""
        with _trace.span(
            "engine.ids_put", cat="engine", bits=self.plan.num_sliced
        ):
            return (
                jnp.asarray(self.plan.slice_bits(ids)), jnp.asarray(valid)
            )

    def _batch_fn(self):
        plan, hoist = self.plan, self.hoist
        ck = ("sess_batch", hoist)
        fn = plan._compiled.get(ck)
        if fn is None:

            @jax.jit
            def fn(arrs, hbufs, bits_, valid_):
                contract = lambda sbits: plan.contract_slice(  # noqa: E731
                    arrs, sbits, hbufs if hoist else None
                )
                contrib = jax.vmap(contract)(bits_)
                with jax.named_scope("batch_sum"):
                    return jnp.sum(mask_invalid(contrib, valid_), axis=0)

            fn = plan._compiled.setdefault(ck, fn)
        return fn

    def compiled_slices(self, n: int):
        """The compiled :meth:`run_slices` program for a batch of ``n``
        ids, for reading its ``memory_analysis()`` and ``as_text()``."""
        return self._batch_fn().lower(
            list(self.arrays), list(self.hoisted()),
            jax.ShapeDtypeStruct((n, self.plan.num_sliced), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
        ).compile()

    # ------------------------------------------------------------------
    # strategy: all slices, scan of vmapped batches (single host)
    # ------------------------------------------------------------------
    def run_all(self, slice_batch: int = 8) -> jnp.ndarray:
        """Sum over all ``2^|S|`` subtasks on one host.

        Subtasks run in vmapped batches of ``slice_batch`` accumulated
        with a ``lax.scan`` so peak memory is bounded; a ragged final
        batch is padded with wrapped-around slice ids masked by the
        validity select.  Within the jitted scan, buffer reclamation is
        driven by the memory plan's deterministic free schedule
        (``_run_steps`` drops each tracer at its planned last use, which
        is what lets XLA's allocator reuse the slot); jit-argument
        donation of the hoisted buffers would be a no-op here — donated
        inputs are only reclaimed via input→output aliasing and the
        scan's sole output is the small amplitude accumulator."""
        plan, hoist, arrays = self.plan, self.hoist, self.arrays
        n_slices = self.n_slices
        if plan.num_sliced == 0:
            key = ("dense",)
            # setdefault: concurrent serving threads race to publish, but
            # all end up calling the one surviving jitted fn (single trace)
            fn = plan._compiled.get(key) or plan._compiled.setdefault(
                key,
                jax.jit(lambda a: plan.contract_slice(a, plan.slice_bits(0))),
            )
            with _trace.span(
                "exec.contract_all", cat="exec", slices=1, hoist=False
            ):
                out = fn(list(arrays))
                _trace.sync(out)
            _metrics.inc("exec.slices_executed", 1)
            _metrics.inc(
                "exec.flops_executed", plan.executed_flops(1, hoist=False)
            )
            return out
        slice_batch = max(1, min(slice_batch, n_slices))
        n_batches = -(-n_slices // slice_batch)
        flat_ids, flat_valid, total = padded_ids(n_slices, slice_batch)
        fn = self._all_fn(slice_batch)
        id_bits = plan.slice_bits(flat_ids).reshape(
            n_batches, slice_batch, plan.num_sliced
        )
        w = flat_valid.reshape(n_batches, slice_batch)
        with _trace.span(
            "exec.contract_all",
            cat="exec",
            slices=n_slices,
            slice_batch=slice_batch,
            hoist=hoist,
            backend=plan.backend,
        ):
            out = fn(
                list(arrays), list(self.hoisted()),
                jnp.asarray(id_bits), jnp.asarray(w),
            )
            _trace.sync(out)
        record_execution(plan, n_slices, total - n_slices, hoist)
        return out

    def _all_fn(self, slice_batch: int):
        """The jitted scan of :meth:`run_all` (memoized on the plan per
        slice batch and hoist mode).  It takes the slice ids' bits,
        ``(n_batches, slice_batch, |S|)``, and the validity mask as
        arguments, so the program's size does not grow with the slice
        count."""
        plan, hoist = self.plan, self.hoist
        padded = self.n_slices % slice_batch != 0
        key = ("all", slice_batch, hoist)
        fn = plan._compiled.get(key)
        if fn is None:

            @jax.jit
            def fn(arrs, hbufs, id_bits, w):
                batched = jax.vmap(
                    lambda sbits: plan.contract_slice(
                        arrs, sbits, hbufs if hoist else None
                    )
                )

                def body(acc, chunk_w):
                    chunk, wk = chunk_w
                    contrib = batched(chunk)
                    if padded:
                        contrib = mask_invalid(contrib, wk)
                    return acc + jnp.sum(contrib, axis=0), None

                out_shape = jax.eval_shape(
                    lambda: jnp.sum(batched(id_bits[0]), axis=0)
                )
                acc0 = jnp.zeros(out_shape.shape, out_shape.dtype)
                acc, _ = jax.lax.scan(body, acc0, (id_bits, w))
                return acc

            fn = plan._compiled.setdefault(key, fn)
        return fn

    # ------------------------------------------------------------------
    # strategy: slice ids sharded over a mesh (shard_map + one psum)
    # ------------------------------------------------------------------
    def _sharded_fn(self, mesh, axis_names: tuple, slice_batch: int):
        """The jitted shard_map program of :meth:`run_sharded` (memoized
        on the plan per mesh, axes, slice batch and hoist mode)."""
        from jax.sharding import PartitionSpec as P

        plan, hoist = self.plan, self.hoist
        key = ("sharded", mesh, axis_names, slice_batch, hoist)
        fn = plan._compiled.get(key)
        if fn is not None:
            return fn
        spec = P(axis_names)

        @jax.jit
        def run(arrs, hbufs, bits_, valid_):
            def worker(bits_local, valid_local):
                # arrs/hbufs are closure captures: replicated devices
                contract = lambda sbits: plan.contract_slice(  # noqa: E731
                    arrs, sbits, hbufs if hoist else None
                )
                batched = jax.vmap(contract)
                idb = bits_local.reshape(-1, slice_batch, plan.num_sliced)
                vb = valid_local.reshape(-1, slice_batch)

                out_shape = jax.eval_shape(
                    lambda: contract(plan.slice_bits(0))
                )

                def body(acc, iv):
                    sbits, ok = iv
                    contrib = batched(sbits)
                    with jax.named_scope("batch_sum"):
                        contrib = mask_invalid(contrib, ok)
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
            )(bits_, valid_)

        # setdefault so concurrent threads converge on one program
        return plan._compiled.setdefault(key, run)

    def run_sharded(
        self, mesh, axis_names: tuple[str, ...] = ("data",),
        slice_batch: int = 1, slice_ids=None,
    ) -> jnp.ndarray:
        """Contract all slices — or just ``slice_ids`` — with
        slice-parallelism over ``axis_names`` (the paper's Sec. V-D).

        Every device scans its chunk of slice ids and contributes to one
        psum; each scan step runs ``slice_batch`` subtasks under ``vmap``.
        Open-batch axes are replicated — only the slice axis is sharded —
        so the one psum returns the complete amplitude batch on every
        device.  The hoisted prologue enters the worker as a replicated
        capture, broadcast once per (leaves, mesh) via the HoistCache;
        the leaves enter replicated too, put once per (session, mesh)
        (:meth:`leaves_replicated`)."""
        plan, hoist = self.plan, self.hoist
        ndev = 1
        for ax in axis_names:
            ndev *= mesh.shape[ax]
        subset = None if slice_ids is None else np.asarray(
            slice_ids, dtype=np.int64
        )
        n_slices = self.n_slices if subset is None else len(subset)
        slice_batch = max(1, min(slice_batch, n_slices))
        # Ragged-batch contract: padding to a multiple of ndev*slice_batch
        # is what guarantees every device's local id chunk reshapes exactly
        # into (n_batches, slice_batch) — no divisibility assumption.
        ids, valid, total = padded_ids(n_slices, ndev * slice_batch)
        if subset is not None:
            ids = subset[ids]

        # invariant prologue: once per process, outside the slice loop
        hoisted = self.hoisted_replicated(mesh) if hoist else []
        leaves = self.leaves_replicated(mesh)

        key = ("sharded", mesh, tuple(axis_names), slice_batch, hoist)
        cached = key in plan._compiled
        fn = self._sharded_fn(mesh, tuple(axis_names), slice_batch)
        with _trace.span(
            "exec.sharded", cat="exec", slices=n_slices, devices=ndev,
            hoist=hoist, cached=cached,
        ):
            bits_d, valid_d = self._put_ids(ids, valid)
            with _trace.span("engine.launch", cat="engine"):
                out = fn(list(leaves), list(hoisted), bits_d, valid_d)
            _trace.sync(out)
        record_execution(plan, n_slices, total - n_slices, hoist)
        return out
