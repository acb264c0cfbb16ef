#!/usr/bin/env python3
"""Readings that set the limit of the correctness check.

    python3 bench/control.py --workload <name> --seeds 12 [--first-seed N]

For each seed, in one process on the chip, at the cell's own size: the
cell's timed entry runs as many calls as a run checks (consecutive ids
from the seed's first id, closed loop), and each call is compared with
the reference (``HIGHEST`` precision) as a run compares it.  Then the
control, the same reference computed one precision lower (``HIGH``'s three
bfloat16 products, where the configuration states float32 at
``HIGHEST``; see ``bench/reference.py``), is put in the program's place on the same ids and compared
the same way.  One JSON line per seed, then a summary: the largest
program reading (the lower end for the limit) and the smallest control
reading (the upper end).  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(workload: str, seeds, *, spec=None, require_tpu=True,
             control_only=False, out=sys.stdout) -> dict:
    """``control_only`` skips the program and reads the control alone
    (on one chip, for a cell whose program needs more)."""
    from bench import circuits, harness, system
    from bench.reference import Reference

    spec = spec if spec is not None else harness.load_spec(ROOT)
    cell, cfg, mix, _ = harness.resolve(spec, workload, ROOT)
    system.add_program(ROOT)
    system.enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.device_info(jax, 1 if control_only else cell["chips"],
                        require_tpu)
    if control_only:
        mix = dict(mix, devices=1)
    n = cfg["rows"] * cfg["cols"]
    per_call = mix["ids_per_call"]
    prog, ctrl = [], []
    for seed in seeds:
        t0 = time.perf_counter()
        ops = circuits.make_circuit(cfg, seed)
        bits = harness.draw_bitstring(seed, n)
        job = system.Job(cfg, mix, ops, bits)
        start = harness.first_id(seed, job.n_slices, per_call)
        calls = [harness.call_ids(start, j, per_call, job.n_slices)
                 for j in range(mix["check_calls"])]
        if control_only:
            results = [(ids, None) for ids in calls]
        else:
            job.hoist()
            results = [(ids, job.call(ids)) for ids in calls]
        problem = job.problem()
        job.close()
        labels, arrays, _ = circuits.network(
            cfg, ops, bits, range(n - mix["open_qubits"], n)
        )
        ref = Reference(problem, labels, arrays)
        p_err = None
        if not control_only:
            p_err, _, _ = harness.check_calls(
                ref, results, seed, len(results), float("inf")
            )
        low = [
            (ids, ref.batch_sum(ids, "bf16x3")[0])
            for ids, _ in results
        ]
        c_err, _, _ = harness.check_calls(
            ref, low, seed, len(low), float("inf")
        )
        prog.append(p_err)
        ctrl.append(c_err)
        print(json.dumps({
            "seed": seed, "program_rel_err": p_err, "control_rel_err": c_err,
            "calls": len(results), "seconds": time.perf_counter() - t0,
        }), file=out, flush=True)
    summary = {
        "workload": workload, "seeds": list(seeds),
        "lower_reading": None if control_only else max(prog),
        "upper_reading": min(ctrl),
        "program_rel_err": prog, "control_rel_err": ctrl,
    }
    print(json.dumps(summary), file=out, flush=True)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--control-only", action="store_true",
                    help="read the control alone, on one chip")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.harness import NoChip

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    try:
        readings(args.workload, seeds, control_only=args.control_only)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
