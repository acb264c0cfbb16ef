"""permute_share: device seconds in the program's ``permute`` scopes
(the operand permutes of each contraction step, opened in
``repro.lowering.gemm_form.contract_flat``) over the device's busy
seconds in the traced window, in percent.

The scope of a device op is read from the ``op_name`` of its instruction
in the compiled program's text (``bench.scopes``).  A program that
names no ``gemm`` scope names no steps, and gives nothing.  Not in
``BENCHMARK.json`` yet: the harness's trace summary lacks the join
(PERF.md, Open questions); ``bench/trace_scopes.py`` reads it."""


def read(ctx):
    tr = ctx["trace"]
    scope_s = (tr or {}).get("scope_s") or {}
    if scope_s.get("gemm", 0.0) <= 0 or sum(tr["busy_s"]) <= 0:
        return None
    return 100.0 * scope_s.get("permute", 0.0) / sum(tr["busy_s"])
