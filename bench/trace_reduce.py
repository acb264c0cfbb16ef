"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The harness wraps its measured window in a host annotation named
``bench.window`` and each call in ``bench.call`` (with ``bench.dispatch``
and ``bench.wait`` inside).  From the trace this module takes:

* the window: the ``bench.window`` span on the host;
* per chip, the device's busy time: the union of the intervals of the
  operations on its ``XLA Ops`` line, clipped to the window;
* device seconds per operation kind (:func:`kind`), summed over chips;
* the ten operations that took most device time, over all chips, each
  named by :func:`short`;
* the ten longest idle gaps of the first chip, each named by the
  innermost ``bench.*`` host span that was open at its midpoint.

``python -m bench.trace_reduce <file>`` prints the summary and, with
``--dump``, the planes, lines and first events of the trace, for
looking at a new device's trace by hand.
"""

from __future__ import annotations

import gzip
import json
import re
import sys

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"

# an op on a TPU's "XLA Ops" line is named by its HLO text:
# "%name = shape opcode(operands), attributes"
_HLO = re.compile(r"%?(?P<name>[\w.\-]+)(?: = (?P<rest>.*))?", re.S)
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv")


def parse(text: str) -> tuple[str, str, str]:
    """``(name, opcode, custom-call target)`` of an op's HLO text; a bare
    name such as ``copy.3`` is its own opcode."""
    m = _HLO.match(text.strip())
    name, rest = m["name"], m["rest"] or ""
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else re.sub(r"\.\d+$", "", name)
    target = _TARGET.search(rest)
    return name, opcode, target.group(1) if target else ""


def kind(text: str) -> str:
    """``collective``; ``transpose`` (data formatting between contraction
    steps: transposes, copies, and reshapes that move data, alone or as a
    fusion named after them); ``matmul`` (XLA dots and convolutions, and
    Pallas kernels, ``tpu_custom_call``); or ``other``."""
    name, op, target = parse(text)
    base = re.sub(r"\.\d+$", "", name)
    if op.startswith(_COLLECTIVE) or (
        op.startswith("async-") and any(c in text for c in _COLLECTIVE)
    ):
        return "collective"
    if op in ("transpose", "copy", "reshape") or (
        op == "fusion" and ("transpose" in base or "copy" in base)
    ):
        return "transpose"
    if op in ("dot", "convolution") or target == "tpu_custom_call" or (
        op == "fusion" and ("convolution" in base or "dot" in base)
    ):
        return "matmul"
    return "other"


def short(text: str) -> str:
    """An op's name, opcode and custom-call target, without its shapes."""
    name, op, target = parse(text)
    return " ".join(x for x in (name, op, target) if x)


def _stats(ev) -> dict:
    try:
        return {str(k): v for k, v in ev.stats}
    except Exception:  # noqa: BLE001 - stats are optional in a trace
        return {}


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _device_planes(pd):
    return [
        p for p in pd.planes
        if p.name.startswith("/device:") and any(
            ln.name == OPS_LINE for ln in p.lines
        )
    ]


def _host_spans(pd) -> list[tuple[str, float, float]]:
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith("bench."):
                    spans.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
    return spans


def load(path: str):
    """A trace file, ``.xplane.pb`` or gzipped ``.xplane.pb.gz``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def summarize(path: str) -> dict:
    """The summary of one trace file (see the module docstring)."""
    pd = load(path)
    spans = _host_spans(pd)
    wins = [(s, e) for n, s, e in spans if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} span in {path}")
    w0, w1 = wins[0]
    planes = _device_planes(pd)
    busy, op_s, per_op = [], {}, {}
    idle = []
    for i, p in enumerate(planes):
        iv = []
        for ln in p.lines:
            if ln.name != OPS_LINE:
                continue
            for ev in ln.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                iv.append((s, e))
                d = (e - s) * 1e-9
                k = kind(ev.name)
                op_s[k] = op_s.get(k, 0.0) + d
                label = short(ev.name)
                per_op[label] = per_op.get(label, 0.0) + d
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [w0] + [x for se in merged for x in se] + [w1]
            idle = [
                (edges[j], edges[j + 1])
                for j in range(0, len(edges), 2)
                if edges[j + 1] > edges[j]
            ]
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        mid = 0.5 * (s + e)
        inner = [
            (t1 - t0, n) for n, t0, t1 in spans
            if t0 <= mid <= t1 and n != WINDOW
        ]
        label = min(inner)[1] if inner else "outside bench calls"
        gaps.append([label, (e - s) * 1e-9])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "chips": len(planes),
        "busy_s": busy,
        "op_s": op_s,
        "top_ops": [[n, s] for n, s in top],
        "gaps": gaps,
    }


def dump(path: str, events: int = 12) -> None:
    pd = load(path)
    for p in pd.planes:
        lines = list(p.lines)
        print("PLANE", p.name, [(ln.name, len(list(ln.events))) for ln in lines])
        if not p.name.startswith("/device:"):
            continue
        for ln in lines:
            for ev in list(ln.events)[:events]:
                print("  ", ln.name, "|", ev.name, ev.start_ns,
                      ev.duration_ns, _stats(ev))


if __name__ == "__main__":
    if "--dump" in sys.argv:
        dump(sys.argv[1])
    print(json.dumps(summarize(sys.argv[1]), indent=1))
