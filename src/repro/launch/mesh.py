"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host
devices *before* any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_host_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Small meshes for tests/examples (must divide available devices)."""
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def multi_host_mesh(axis_name: str = "data"):
    """One flat mesh over every *global* device of a ``jax.distributed``
    run — the data-parallel axis the multi-host transport reduces over.

    Call :func:`repro.distributed.init_multi_host` first in an N-process
    launch; at world size 1 this degenerates to a mesh over the local
    devices, so the same code path serves both (the world-size-1
    invariance contract)."""
    import numpy as np
    from jax.sharding import Mesh

    devices = np.asarray(jax.devices())
    return Mesh(devices, (axis_name,), **_axis_kwargs(1))


def main(argv=None) -> int:
    """CI smoke entry point: ``python -m repro.launch.mesh`` prints this
    process's world view and proves a cross-process psum round-trips.
    Run as N plain subprocesses with ``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` set (no mpirun)."""
    import argparse

    from ..distributed.transport import init_multi_host

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--coordinator", default=None)
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    args = p.parse_args(argv)
    rank, size = init_multi_host(
        args.coordinator, args.num_processes, args.process_id
    )
    mesh = multi_host_mesh()
    from ..distributed.transport import CollectiveTransport

    tp = CollectiveTransport(mesh=mesh, chunks=1)
    tp.rounds = 1
    import numpy as np

    tp.push(np.asarray([float(rank + 1)], dtype=np.float32))
    total = tp.finalize()
    expect = size * (size + 1) / 2
    ok = total is not None and float(total[0]) == expect
    print(
        f"mesh-smoke rank={rank}/{size} devices={len(jax.devices())} "
        f"psum={float(total[0]) if total is not None else None} "
        f"{'OK' if ok else 'FAIL'}"
    )
    return 0 if ok else 1


if __name__ == "__main__":  # pragma: no cover - exercised by CI leg
    raise SystemExit(main())
