"""Span tracer: thread-local span stacks, profiler-clock wall, JSONL export.

Design constraints (the acceptance contract of the observability PR):

  * **Zero-overhead off path.**  ``REPRO_TRACE=0`` (the default) makes
    :func:`span` return a shared no-op context manager and makes
    :func:`sync` / :func:`instant` early-return on one boolean check.
    Instrumentation lives at the Python orchestration layer only —
    the flag never reaches jit-traced code — so compiled artifacts and
    plan fingerprints are bitwise-identical with tracing on or off.
  * **Well-formed span trees.**  Spans nest on a thread-local stack:
    every record carries its parent's id, and per thread the intervals
    are properly nested (children inside parents, siblings
    non-overlapping) because enter/exit order is stack order.
  * **XLA profile passthrough, one clock.**  An active span also enters
    ``jax.profiler.TraceAnnotation(name)``, so the same names show up on
    the host timeline of an XLA profile when one is being captured.
    Records are stamped on the clock the profiler stamps its host
    events with (the Unix-epoch clock of ``time.time_ns``; an xplane's
    event times are offsets from its ``profile_start_time`` on that
    clock), through one offset from ``time.perf_counter`` taken at
    import, so durations stay monotonic and a record lands where its
    annotation does.
  * **Sync points.**  Wall times at phase boundaries are only meaningful
    once dispatched work retires; :func:`sync` is
    ``jax.block_until_ready`` gated on the tracing flag, so enabling
    tracing adds the barriers and disabling it restores fully async
    dispatch.

Export is Chrome-trace-event JSONL (one complete-event object per
line) via :func:`dump_trace`; ``fmt="chrome"`` wraps the same events as
``{"traceEvents": [...]}`` which Perfetto / ``chrome://tracing`` open
directly.  :func:`merge_traces` concatenates per-process JSONL files
(each record carries its pid) into one timeline — the multi-process
merge step for ``contract_sharded``-style runs.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import threading
import time

#: recorded spans are dropped beyond this cap (a long traced test session
#: must not grow memory without bound); drops are counted in
#: ``metrics`` under ``trace.dropped_spans``.
MAX_SPANS = 200_000


#: profiler clock minus ``time.perf_counter``, in seconds, taken once
_CLOCK_OFFSET_S = (time.time_ns() - time.perf_counter_ns()) * 1e-9


def _now() -> float:
    """Seconds on the profiler's clock, advancing as ``perf_counter``."""
    return time.perf_counter() + _CLOCK_OFFSET_S


def _env_enabled() -> bool:
    v = os.environ.get("REPRO_TRACE", "0")
    if v not in ("0", "1"):
        raise ValueError(f"REPRO_TRACE={v!r} not in ('0', '1')")
    return v == "1"


_enabled = _env_enabled()
_lock = threading.Lock()
_records: list[SpanRecord] = []
_ids = itertools.count(1)
_tls = threading.local()
_jax = None  # lazily imported once; obs must stay importable without jax


def enabled() -> bool:
    """Whether tracing is currently on (``REPRO_TRACE`` at import time,
    overridable via :func:`set_enabled` / :class:`enabled_scope`)."""
    return _enabled


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


class enabled_scope:
    """Temporarily force tracing on/off (``None`` leaves it unchanged) —
    the implementation of the API layer's per-call ``telemetry=``
    toggle.  Process-global, like the flag itself: overlapping scopes
    from concurrent threads see last-writer-wins, the documented
    limitation of a per-call toggle on a process-global tracer."""

    def __init__(self, on: bool | None):
        self.on = on
        self._prev = None

    def __enter__(self):
        if self.on is not None:
            self._prev = _enabled
            set_enabled(self.on)
        return self

    def __exit__(self, *exc):
        if self._prev is not None:
            set_enabled(self._prev)
        return False


@dataclasses.dataclass
class SpanRecord:
    """One finished span (flat record; the tree is in ``parent_id``)."""

    span_id: int
    parent_id: int  # 0 = top-level span of its thread
    name: str
    cat: str
    t_start: float  # seconds on the profiler's clock (see _now)
    t_end: float
    thread: int
    pid: int
    attrs: dict

    @property
    def dur_s(self) -> float:
        return self.t_end - self.t_start

    def event(self) -> dict:
        """Chrome trace 'complete' event (Perfetto-compatible)."""
        args = dict(self.attrs)
        args["span_id"] = self.span_id
        args["parent_id"] = self.parent_id
        return {
            "name": self.name,
            "cat": self.cat,
            "ph": "X",
            "ts": self.t_start * 1e6,
            "dur": self.dur_s * 1e6,
            "pid": self.pid,
            "tid": self.thread,
            "args": args,
        }


class _Noop:
    """Shared do-nothing span/annotation for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _Noop()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class _Span:
    __slots__ = (
        "name", "cat", "attrs", "span_id", "parent_id", "t0", "_ann",
    )

    def __init__(self, name: str, cat: str, attrs: dict):
        self.name = name
        self.cat = cat
        self.attrs = attrs

    def set(self, **attrs):
        """Attach attributes to a live span (measured values only become
        known mid-span, e.g. a cache hit discovered after the lookup)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        global _jax
        st = _stack()
        self.parent_id = st[-1].span_id if st else 0
        self.span_id = next(_ids)
        st.append(self)
        self._ann = None
        if _jax is None:
            try:
                import jax

                _jax = jax
            except Exception:  # pragma: no cover - jax is a hard dep here
                _jax = False
        if _jax:
            self._ann = _jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc):
        t1 = _now()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        st = _stack()
        # tolerate exits out of stack order (a generator holding a span
        # across yields): unwind to this span if present
        if self in st:
            while st and st[-1] is not self:
                st.pop()
            st.pop()
        rec = SpanRecord(
            span_id=self.span_id,
            parent_id=self.parent_id,
            name=self.name,
            cat=self.cat,
            t_start=self.t0,
            t_end=t1,
            thread=threading.get_ident(),
            pid=os.getpid(),
            attrs=self.attrs,
        )
        with _lock:
            if len(_records) < MAX_SPANS:
                _records.append(rec)
            else:
                from . import metrics  # local: avoid import cycle at init

                metrics.REGISTRY.counter("trace.dropped_spans").inc(1)
        return False


def span(name: str, cat: str = "span", **attrs):
    """Context manager recording one span.  No-op (shared stub, no
    allocation beyond the kwargs dict) when tracing is off."""
    if not _enabled:
        return _NOOP
    return _Span(name, cat, attrs)


def traced(name: str | None = None, cat: str = "fn"):
    """Decorator form of :func:`span` (checks the flag per call, so a
    decorated function stays zero-overhead while tracing is off)."""
    import functools

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _enabled:
                return fn(*args, **kwargs)
            with _Span(label, cat, {}):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def sync(x):
    """Phase-boundary sync point: ``jax.block_until_ready`` when tracing
    is on (span walls then measure retired work, not dispatch), identity
    when off (async dispatch untouched)."""
    if not _enabled:
        return x
    global _jax
    if _jax is None:
        try:
            import jax

            _jax = jax
        except Exception:  # pragma: no cover
            _jax = False
    if _jax:
        _jax.block_until_ready(x)
    return x


def instant(name: str, cat: str = "instant", **attrs) -> None:
    """Zero-duration event (structured log records ride on these)."""
    if not _enabled:
        return
    t = _now()
    st = _stack()
    rec = SpanRecord(
        span_id=next(_ids),
        parent_id=st[-1].span_id if st else 0,
        name=name,
        cat=cat,
        t_start=t,
        t_end=t,
        thread=threading.get_ident(),
        pid=os.getpid(),
        attrs=attrs,
    )
    with _lock:
        if len(_records) < MAX_SPANS:
            _records.append(rec)


def get_spans() -> list[SpanRecord]:
    """Finished spans recorded so far (snapshot copy)."""
    with _lock:
        return list(_records)


def reset() -> None:
    """Drop all recorded spans (open spans on any stack still record on
    exit)."""
    with _lock:
        _records.clear()


def summary() -> dict:
    """Per-name aggregates: ``{name: {count, total_s, max_s}}``."""
    out: dict[str, dict] = {}
    for rec in get_spans():
        agg = out.setdefault(
            rec.name, {"count": 0, "total_s": 0.0, "max_s": 0.0}
        )
        agg["count"] += 1
        agg["total_s"] += rec.dur_s
        agg["max_s"] = max(agg["max_s"], rec.dur_s)
    return out


def dump_trace(path: str, fmt: str = "jsonl") -> int:
    """Write all recorded spans to ``path``; returns the event count.

    ``fmt="jsonl"`` (default): one Chrome-trace complete-event object
    per line — greppable, appendable, mergeable across processes.
    ``fmt="chrome"``: the same events wrapped as
    ``{"traceEvents": [...]}`` — open directly in Perfetto
    (https://ui.perfetto.dev) or ``chrome://tracing``.  A JSONL file is
    converted losslessly by wrapping its lines in a JSON array.
    """
    events = [rec.event() for rec in get_spans()]
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        if fmt == "jsonl":
            for ev in events:
                f.write(json.dumps(ev) + "\n")
        elif fmt == "chrome":
            json.dump({"traceEvents": events}, f)
        else:
            raise ValueError(f"unknown trace format {fmt!r}")
    return len(events)


def merge_traces(paths, out_path: str) -> int:
    """Merge per-process JSONL traces into one JSONL timeline.

    Each event already carries its producer's ``pid``, so merging is
    concatenation; Perfetto renders distinct pids as distinct process
    tracks.  This is the span-merging step for multi-process
    ``contract_sharded`` runs: every process dumps its own file, one
    merge yields the cluster timeline.  Returns the merged event count.
    """
    events: list[dict] = []
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    events.sort(key=lambda ev: ev.get("ts", 0.0))
    d = os.path.dirname(out_path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(out_path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    return len(events)
