"""gemm_roofline: the least time one slice's multiply-adds could take
on this chip over the device time one slice spent in the program's
``gemm`` scopes, in percent.

The least time is the flops of :func:`bench.metrics.slice_roofline.
slice_work` (the same least work ``slice_roofline`` reads, whatever
kernel runs a step) at the chip's bf16 peak.  The ``gemm`` scope
(``repro.lowering.gemm_form.contract_flat``) holds each step's Pallas
kernel or XLA dot with its Karatsuba sums and differences; its device
seconds, summed over chips, are divided by the slices completed in the
traced window.

Nothing is read where the program names no ``gemm`` scope, or where
more than 1% of the matmul-kind device time (``bench.trace_reduce.kind``)
lies outside ``gemm`` scopes: a kernel the join missed would shrink the
time and inflate the share; the reader's notes then say why
(``gemm_roofline_none``).  Read from ``bench.scopes``'s summary; not in
``BENCHMARK.json`` yet (PERF.md, Open questions)."""

from bench.metrics.slice_roofline import slice_work

# the share of matmul-kind device time that may lie outside gemm scopes
OUTSIDE_LIMIT = 0.01


def read(ctx):
    tr = ctx["trace"]
    n = ctx.get("slices_traced", 0)
    gemm_s = ((tr or {}).get("scope_s") or {}).get("gemm", 0.0)
    if n <= 0 or gemm_s <= 0:
        return None
    matmul = tr["op_s"].get("matmul", 0.0)
    outside = matmul - tr["scope_op_s"].get("gemm", {}).get("matmul", 0.0)
    if outside > OUTSIDE_LIMIT * matmul:
        ctx.setdefault("notes", {})["gemm_roofline_none"] = (
            f"{100.0 * outside / matmul:.3g}% of matmul-kind device time "
            "lies outside gemm scopes"
        )
        return None
    peaks = ctx["peaks"]
    work = slice_work(ctx["problem"], peaks["vmem_bytes"])
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    return 100.0 * t_flops / (gemm_s / n)
