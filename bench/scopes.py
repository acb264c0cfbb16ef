"""Join a profiler trace with the compiled program's text: device seconds
by the program's named scopes and contraction steps, and the program's
own host spans.

The program opens a ``jax.named_scope`` around each contraction step
(``step<k>.<backend>``, ``k`` its position in ``plan.steps``) and its
parts (``permute``, ``gemm``), and around ``leaves``, ``output``,
``batch_sum`` and ``prologue`` (``repro.core.executor``).  A TPU trace
names a device op by its HLO instruction alone; the instruction's
``op_name`` metadata in the compiled program's ``as_text()`` carries the
scopes.  With its tracing on (``repro.obs.trace.enabled_scope``), the
program also enters a profiler annotation for each of its spans, the
``engine.*`` spans of ``ContractionSession.run_slices`` among them, on
the trace's own clock.

:func:`summarize` returns :func:`bench.trace_reduce.summarize`'s summary
of the same trace, unchanged but for two relabellings, and more keys:

* ``scope_s``: device seconds per scope (:func:`scope_of`): ``permute``,
  ``gemm``, ``leaves``, ``output``, ``batch_sum``, ``prologue``,
  ``arguments`` (ops XLA put on a program argument, which carry its
  name: the split of a complex argument into real parts, a copy into
  another layout or memory) or ``unscoped``, summed over chips;
  ``scope_op_s`` splits each by :func:`bench.trace_reduce.kind`;
* ``step_s``: the ten costliest contraction steps, each with its backend
  and its gemm and permute seconds;
* ``unmatched_s``: device seconds of ops whose instruction the text
  lacks (counted as ``unscoped``; above 0, the text is of another
  program);
* ``program_spans``: the durations of the ``engine.*`` host spans that
  started in the window, by name;
* each of ``top_ops`` ends in `` @step<k>.<backend>/<scope>``, or
  `` @<scope>`` outside the steps;
* each of ``gaps`` is named by the innermost ``bench.*`` or ``engine.*``
  span open at its midpoint.

The harness does not call it yet (PERF.md, Open questions);
``bench/trace_scopes.py`` runs a cell's traced window with the program's
spans on and prints it with the readings of ``permute_share``,
``gemm_roofline`` and ``launch_ms`` (``bench/metrics/``).
"""

from __future__ import annotations

import re

from bench import trace_reduce as tr

# host spans: the benchmark's own and the program's engine spans
SPAN_PREFIXES = ("bench.", "engine.")
# the program's scopes; the innermost on an op's path wins
SCOPES = ("permute", "gemm", "leaves", "output", "batch_sum", "prologue")
# ops XLA puts on a program argument carry the argument's name
ARGUMENTS = "arguments"
_STEP = re.compile(r"step(\d+)\.(\w+)")
# one op_name path component; a transform wraps it: "vmap(gemm)"
_COMPONENT = re.compile(r"(?:[a-z_]+\()*([\w.\-]+)\)*")
# an instruction of HLO text: its name and the rest of its line
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r"\bop_name=\"([^\"]*)\"")
_OPERAND = re.compile(r"\(%([\w.\-]+)")


def scope_of(op_name: str) -> tuple[str, str, str]:
    """``(scope, step, backend)`` of an op's ``op_name``: the innermost
    of :data:`SCOPES` on its path (else ``unscoped``), and the
    ``step<k>.<backend>`` scope around it (else ``""``, ``""``).  XLA
    joins the names of ops it merged with ``;``: the first one counts."""
    scope, step, backend = "unscoped", "", ""
    for comp in op_name.split(";")[0].split("/"):
        if comp.startswith(("jit(", "pjit(")):
            continue
        m = _COMPONENT.fullmatch(comp)
        name = m.group(1) if m else comp
        if name in SCOPES:
            scope = name
        st = _STEP.fullmatch(name)
        if st:
            step, backend = st.group(1), st.group(2)
    return scope, step, backend


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name``, over every computation of an HLO
    module's text.  An instruction without one (an async copy's start or
    done that XLA added) takes its first operand's."""
    own, operand = {}, {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(rest)
        own[name] = m.group(1) if m else None
        m = _OPERAND.search(rest)
        operand[name] = m.group(1) if m else None
    out = {}
    for name in own:
        n, hops = name, 0
        while own.get(n) is None and operand.get(n) and hops < 8:
            n, hops = operand[n], hops + 1
        out[name] = own.get(n) or ""
    return out


def arguments(hlo_text: str) -> frozenset[str]:
    """The ``op_name``s of the entry computation's parameters: the
    program's arguments (``arrs[3]``, ``ids_``)."""
    entry = hlo_text[hlo_text.find("\nENTRY "):]
    entry = entry[:entry.find("\n}")]
    return frozenset(
        m.group(1) for _, rest in _INSTRUCTION.findall(entry)
        if " parameter(" in rest and (m := _OP_NAME.search(rest))
    )


class _Tally:
    """Device seconds by scope and step."""

    def __init__(self, hlo_text: str):
        self.names = op_names(hlo_text)
        self.args = arguments(hlo_text)
        self.scope_s: dict[str, float] = {}
        self.scope_op_s: dict[str, dict[str, float]] = {}
        self.steps: dict[str, dict] = {}
        self.unmatched_s = 0.0

    def scope(self, instruction: str) -> tuple[str, str, str]:
        op_name = self.names.get(instruction, "")
        if op_name.split(";")[0] in self.args:
            return ARGUMENTS, "", ""
        return scope_of(op_name)

    def suffix(self, instruction: str) -> str:
        scope, step, backend = self.scope(instruction)
        if step:
            return f" @step{step}.{backend}/{scope}"
        return f" @{scope}"

    def add(self, text: str, d: float) -> None:
        name = tr.parse(text)[0]
        if name not in self.names:
            self.unmatched_s += d
        scope, step, backend = self.scope(name)
        self.scope_s[scope] = self.scope_s.get(scope, 0.0) + d
        by_kind = self.scope_op_s.setdefault(scope, {})
        k = tr.kind(text)
        by_kind[k] = by_kind.get(k, 0.0) + d
        if step:
            row = self.steps.setdefault(step, {
                "step": int(step), "backend": backend, "s": 0.0,
                "gemm_s": 0.0, "permute_s": 0.0,
            })
            row["s"] += d
            if scope in ("gemm", "permute"):
                row[scope + "_s"] += d


def host_spans(pd) -> list[tuple[str, float, float]]:
    """``(name, start, end)`` of every ``bench.*`` and ``engine.*`` host
    event, in the trace's nanoseconds."""
    return [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for p in pd.planes if p.name.startswith("/host:")
        for ln in p.lines for ev in ln.events
        if ev.name.startswith(SPAN_PREFIXES)
    ]


def summarize(path: str, hlo_text: str) -> dict:
    """The summary of one trace file joined with the text of the
    program that ran in it (see the module docstring)."""
    out = tr.summarize(path)
    pd = tr.load(path)
    spans = host_spans(pd)
    w0, w1 = next((s, e) for n, s, e in spans if n == tr.WINDOW)
    tally = _Tally(hlo_text)
    idle = []
    for i, p in enumerate(tr._device_planes(pd)):
        iv = []
        for ln in p.lines:
            if ln.name != tr.OPS_LINE:
                continue
            for ev in ln.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    iv.append((s, e))
                    tally.add(ev.name, (e - s) * 1e-9)
        if i == 0:
            edges = [w0] + [x for se in tr._union(iv) for x in se] + [w1]
            idle = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        mid = 0.5 * (s + e)
        inner = [(t1 - t0, n) for n, t0, t1 in spans
                 if t0 <= mid <= t1 and n != tr.WINDOW]
        gaps.append([min(inner)[1] if inner else "outside bench calls",
                     (e - s) * 1e-9])
    program: dict[str, list[float]] = {}
    for n, t0, t1 in spans:
        if n.startswith("engine.") and w0 <= t0 <= w1:
            program.setdefault(n, []).append((t1 - t0) * 1e-9)
    out.update(
        top_ops=[[n + tally.suffix(n.split()[0]), s]
                 for n, s in out["top_ops"]],
        gaps=gaps,
        scope_s=tally.scope_s,
        scope_op_s=tally.scope_op_s,
        step_s=sorted(tally.steps.values(), key=lambda r: -r["s"])[:10],
        unmatched_s=tally.unmatched_s,
        program_spans=program,
    )
    return out
