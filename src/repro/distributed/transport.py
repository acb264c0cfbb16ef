"""Multi-process transport: ``jax.distributed`` init + overlapped
chunked all-reduce.

The paper ends every sliced contraction with "only one all-reduce
operation ... after the computation" — a terminal barrier.  "Closing the
gap" (arXiv 2110.14502) showed the cross-node reduction can instead be
overlapped with the remaining slice computation.  This module provides
that as a transport abstraction the multi-host driver composes with the
scheduler:

  * :func:`init_multi_host` wraps ``jax.distributed.initialize`` with
    gloo CPU collectives, env-var defaults (``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``), and a no-op
    single-process path — the same script runs unchanged as 1 or N CPU
    processes (the CI matrix leg launches 2);
  * :class:`CollectiveTransport` reduces the partial amplitude in a
    **fixed number of rounds × chunks** of psum calls.  Fixing the call
    count up front is what makes overlapping safe under work stealing:
    hosts execute *different* numbers of slice batches, but every host
    dispatches the identical sequence of collectives (zero-padded when
    its work ran out), so gloo's order-matched rendezvous can never
    deadlock.  Rounds are dispatched asynchronously mid-run — jax's
    async dispatch reduces round ``r`` on the collective thread while
    the host's Python thread is already dispatching the next slice
    batch — and only :meth:`finalize` blocks, yielding the measured
    ``overlap_fraction``;
  * :class:`FileTransport` is the collective-free control-plane-only
    fallback: partials travel through the elastic claim store's merged
    checkpoint (a host crash can never hang a rendezvous — the
    host-failure resume test runs on this transport);
  * :class:`NullTransport` is world-size-1: local sum, zero overhead.
"""

from __future__ import annotations

import os
import time

import numpy as np


def world() -> tuple[int, int]:
    """(process_index, process_count) of the current jax runtime."""
    import jax

    return jax.process_index(), jax.process_count()


def init_multi_host(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> tuple[int, int]:
    """Initialize ``jax.distributed`` for an N-process CPU/TPU run.

    Arguments default to ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES``
    / ``REPRO_PROCESS_ID``; with no configuration at all (or
    ``num_processes == 1``) this is a no-op and the run stays
    single-process — the world-size-1 invariance contract.  On CPU the
    gloo collectives backend is selected *before* backend init so
    cross-process psum works without MPI (xpc-free: plain subprocesses).
    Returns ``(process_index, process_count)``."""
    coordinator = coordinator or os.environ.get("REPRO_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("REPRO_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("REPRO_PROCESS_ID", "0"))
    if num_processes <= 1 or coordinator is None:
        return world()
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return world()


class Transport:
    """Reduction transport for the multi-host driver.

    The driver calls :meth:`push` exactly ``rounds`` times per host with
    the local partial-sum *delta* accumulated since the previous push
    (zeros when the host's work has drained), then :meth:`finalize` once
    for the fully reduced value.  ``overlap_fraction`` is only
    meaningful after finalize."""

    #: number of push rounds the driver must emit (uniform across hosts)
    rounds: int = 1
    overlap_fraction: float = 0.0

    def push(self, delta) -> None:
        raise NotImplementedError

    def finalize(self):
        raise NotImplementedError


class NullTransport(Transport):
    """World-size-1: the local accumulator *is* the reduction."""

    def __init__(self, rounds: int = 1):
        self.rounds = max(1, int(rounds))
        self._acc = None

    def push(self, delta) -> None:
        d = np.asarray(delta)
        self._acc = d if self._acc is None else self._acc + d

    def finalize(self):
        return self._acc


class CollectiveTransport(Transport):
    """Chunked, overlapped cross-process all-reduce via shard_map psum.

    The complex accumulator is viewed as a flat float32/float64 buffer,
    zero-padded to ``chunks`` equal pieces (one traced program serves
    every chunk), and each :meth:`push` dispatches ``chunks`` psum calls
    *without blocking* — on CPU the gloo rendezvous runs on XLA's
    execution threads while Python keeps dispatching compute.
    :meth:`finalize` blocks on all outstanding reductions, sums the
    rounds, and restores shape/dtype.

    ``overlap_fraction`` = 1 − (blocked wall in finalize) / (wall from
    the first push to the end of finalize): 1.0 means the reduction was
    fully hidden behind slice compute, 0.0 means it degenerated to the
    paper's terminal barrier."""

    def __init__(self, mesh=None, axis_name: str = "data", chunks: int = 4):
        import jax

        if mesh is None:
            from ..launch.mesh import multi_host_mesh

            mesh = multi_host_mesh(axis_name)
        self.mesh = mesh
        self.axis_name = axis_name
        self.chunks = max(1, int(chunks))
        # the local delta enters the shard_map replicated (in_specs=P()),
        # so every *local* device contributes a copy to the psum; scale
        # by this process's device count in the mesh so each process's
        # delta is counted exactly once (exact for power-of-2 counts)
        me = jax.process_index()
        self._nlocal = max(
            1,
            sum(
                1 for d in np.asarray(mesh.devices).flat
                if d.process_index == me
            ),
        )
        self.rounds = 1  # driver overrides before the run starts
        self._pending: list = []  # per round: list of reduced chunk arrays
        self._template = None  # (shape, dtype, view_dtype, flat_len)
        self._t_first_push = None
        self._reduce = None
        self._jax = jax

    # -- lazily traced collective (one program, every chunk reuses it) --
    def _reducer(self):
        if self._reduce is None:
            import jax
            from jax.sharding import PartitionSpec as P

            axis = self.axis_name

            def psum_chunk(x):
                return jax.lax.psum(x, axis)

            self._reduce = jax.jit(
                jax.shard_map(
                    psum_chunk,
                    mesh=self.mesh,
                    in_specs=P(),
                    out_specs=P(),
                    check_vma=False,
                )
            )
        return self._reduce

    @staticmethod
    def _as_flat(d, view):
        """Flatten to a 1-d real view (complex dtypes reinterpreted as
        interleaved re/im pairs — gloo reduces real buffers only)."""
        flat = np.ascontiguousarray(d).reshape(-1)
        if d.dtype.kind == "c":
            return flat.view(view)
        return flat.astype(view, copy=False)

    def push(self, delta) -> None:
        import jax.numpy as jnp

        d = np.asarray(delta)
        if self._template is None:
            view = np.float64 if d.dtype == np.complex128 else np.float32
            flat = self._as_flat(d, view)
            pad = -len(flat) % self.chunks
            self._template = (d.shape, d.dtype, view, len(flat), pad)
        shape, dtype, view, n, pad = self._template
        flat = self._as_flat(d, view) / view(self._nlocal)
        if pad:
            flat = np.concatenate([flat, np.zeros(pad, view)])
        if self._t_first_push is None:
            self._t_first_push = time.perf_counter()
        reduce = self._reducer()
        csize = len(flat) // self.chunks
        outs = [
            reduce(jnp.asarray(flat[i * csize:(i + 1) * csize]))
            for i in range(self.chunks)
        ]
        self._pending.append(outs)

    def finalize(self):
        import jax

        if not self._pending:
            return None
        t0 = time.perf_counter()
        jax.block_until_ready(self._pending)
        t_block = time.perf_counter() - t0
        window = time.perf_counter() - (self._t_first_push or t0)
        self.overlap_fraction = (
            max(0.0, 1.0 - t_block / window) if window > 0 else 0.0
        )
        shape, dtype, view, n, pad = self._template
        total = None
        for outs in self._pending:
            flat = np.concatenate([np.asarray(o) for o in outs])[:n]
            total = flat if total is None else total + flat
        if np.dtype(dtype).kind == "c":
            return total.view(dtype).reshape(shape)
        return total.astype(dtype).reshape(shape)


class FileTransport(Transport):
    """Reduce through the elastic claim store's merged checkpoint.

    The driver already persists every completed range's partial delta to
    the store (that is the fault-tolerance contract), so the reduction
    is simply the merged checkpoint's partial sum — no collectives, no
    rendezvous to hang when a host dies mid-run.  ``finalize`` returns
    the merged partial *regardless of coverage*; the driver checks
    coverage and reports incompleteness (a dead host's unfinished ids
    stay missing until a resumed run steals them)."""

    def __init__(self, store):
        self.store = store
        self.rounds = 1

    def push(self, delta) -> None:  # partials travel via the store
        pass

    def finalize(self):
        state = self.store.merged()
        return np.asarray(state.partial)
