"""Every call the benchmark makes into the program under test.

The program is the ``repro`` package under ``<checkout>/src``.  The
benchmark drives it only through the entries below, so a change to any
of them is a change to the benchmark's interface:

* ``repro.launch.compile_cache.enable_compile_cache``
* ``repro.launch.mesh.make_host_mesh`` (the four-chip mesh)
* ``repro.quantum.circuits.Circuit`` / ``GateOp`` (the circuit handed in)
* ``repro.core.api.open_session`` (amplitude jobs)
* ``repro.sampling.batch.open_batch_network`` and
  ``repro.core.api.plan_compiled`` (open-qubit batch jobs)
* ``repro.engine.session.ContractionSession``: ``run_slices``,
  ``run_sharded``, ``compiled_slices``, ``hoisted``, ``plan``
* the plan's ``tn.inputs``, ``tn.space.labels``, ``tree.children``,
  ``tree.contract_order()``, ``tree.root``, ``sliced_bits``, ``out_inds``
  and ``contract_prologue``.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np


def add_program(root: str) -> None:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache() -> str:
    from repro.launch.compile_cache import enable_compile_cache as enable

    return enable()


class Job:
    """One configuration's network planned and bound to its leaves,
    ready to run the mix's calls."""

    def __init__(self, cfg: dict, mix: dict, ops, bitstring: str):
        from repro.core.api import open_session, plan_compiled
        from repro.engine.session import ContractionSession
        from repro.quantum.circuits import Circuit, GateOp

        n = cfg["rows"] * cfg["cols"]
        circ = Circuit(n, [GateOp(g, tuple(qs)) for g, qs in ops])
        opts = dict(cfg["plan"])
        k = mix.get("open_qubits", 0)
        self.open_qubits = tuple(range(n - k, n)) if k else ()
        t0 = time.perf_counter()
        if k:
            from repro.sampling.batch import open_batch_network

            tn, arrays = open_batch_network(circ, bitstring, self.open_qubits)
            td = max(opts.pop("target_dim"), k + 1)
            plan, _ = plan_compiled(tn, td, dtype=arrays[0].dtype, **opts)
            self.session = ContractionSession(plan, arrays)
        else:
            td = opts.pop("target_dim")
            self.session, _ = open_session(
                circ, bitstring, target_dim=td, **opts
            )
        self.plan_s = time.perf_counter() - t0
        self.plan = self.session.plan
        self.n_slices = 1 << self.plan.num_sliced
        self.slice_batch = mix["slice_batch"]
        self.devices = mix.get("devices", 1)
        self.mesh = None
        if self.devices > 1:
            from repro.launch.mesh import make_host_mesh

            self.mesh = make_host_mesh((self.devices,), ("data",))

    def problem(self) -> dict:
        """The network as plain data: leaf label tuples, tree pairs in
        execution order, the root, the sliced wires in slice-id bit
        order, and the output wires."""
        plan = self.plan
        tree = plan.tree
        return {
            "leaves": [tuple(t) for t in plan.tn.inputs],
            "pairs": [
                (*tree.children[v], v) for v in tree.contract_order()
            ],
            "root": tree.root,
            "sliced": [plan.tn.space.labels[b] for b in plan.sliced_bits],
            "out": tuple(plan.out_inds),
        }

    def hoist(self) -> float:
        """Materialize the slice-invariant prologue (compiles it), then
        time one more execution of it alone."""
        import jax

        self.session.hoisted()
        if not self.session.hoist:
            return 0.0
        t0 = time.perf_counter()
        jax.block_until_ready(
            self.plan.contract_prologue(self.session.arrays, use_cache=False)
        )
        return time.perf_counter() - t0

    def compiled_bytes(self) -> int:
        """Bytes the compiled one-device slice-batch program holds:
        arguments, outputs and temporaries."""
        ma = self.session.compiled_slices(self.slice_batch).memory_analysis()
        return int(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes
        )

    def dispatch(self, ids):
        """Start one call on ``ids``; returns the device result."""
        if self.mesh is None:
            return self.session.run_slices(ids)
        return self.session.run_sharded(
            self.mesh, ("data",), slice_batch=self.slice_batch,
            slice_ids=ids,
        )

    def call(self, ids) -> np.ndarray:
        import jax

        return np.asarray(jax.block_until_ready(self.dispatch(ids)))

    def close(self) -> None:
        self.session = None
        self.plan = None
