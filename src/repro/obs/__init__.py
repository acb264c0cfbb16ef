"""Observability: span tracing, metrics, structured logging, calibration.

The measurement substrate for every perf claim the reproduction makes
(the paper's 308.6 Pflops / 96.1 s Sycamore headlines are *measurement*
claims — Sec. VI).  Three parts:

  * :mod:`repro.obs.trace` — low-overhead span tracer: context-manager /
    decorator spans on a thread-local stack, monotonic wall clocks on
    the profiler's time axis, optional ``jax.block_until_ready`` sync
    points at phase boundaries, ``jax.profiler.TraceAnnotation``
    passthrough (spans show up in XLA profiles, where the records
    line up with them), JSONL export readable by Perfetto.
  * :mod:`repro.obs.metrics` — process-global named counters / gauges /
    histograms (plan-cache and HoistCache hits/misses/evicted bytes,
    slices executed, executed FLOPs, ragged-padding
    waste; the multi-host scheduler adds per-host queue depth gauges
    ``sched.queue_depth.h<h>``, the ``sched.steals`` counter, the
    ``sched.steal_latency_s`` histogram — drain-to-claim latency of
    each successful steal — and the elastic store's
    ``elastic.ranges_completed`` / ``elastic.claims_reclaimed``),
    snapshot-able as a dict and reset-able for tests.
  * :mod:`repro.obs.calibrate` — joins per-node measured wall against
    the refiner's modeled times and the lifetime planner's certified
    peaks into a model-vs-measured table per backend class — the
    feedback signal the adaptive refiner and work-stealing scheduler
    need (ROADMAP).

Everything is gated by ``REPRO_TRACE={0,1}`` (default off).  The off
path is no-op stubs at the Python orchestration layer — the flag never
reaches a jitted program, so plan fingerprints and compiled artifacts
are bitwise-unchanged whether tracing is on or off.  Inside the
programs, ``jax.named_scope``s name each contraction step and its parts
(always on: they change only op metadata, see
:mod:`repro.core.executor`), which is how a device profile's ops are
attributed.
"""

from __future__ import annotations

from . import calibrate, log, metrics, trace  # noqa: F401
from .calibrate import CalibrationReport, calibrate_plan  # noqa: F401
from .trace import (  # noqa: F401
    dump_trace,
    enabled,
    enabled_scope,
    get_spans,
    merge_traces,
    set_enabled,
    span,
    sync,
)


def telemetry_summary() -> dict:
    """Compact snapshot of the current telemetry state — what
    ``PlanReport.telemetry`` carries when a ``telemetry=``/``REPRO_TRACE``
    run asks for it: the full metrics snapshot plus per-span-name
    count/total-wall aggregates (never the raw span list — that is what
    :func:`repro.obs.trace.dump_trace` is for)."""
    return {"metrics": metrics.snapshot(), "spans": trace.summary()}


def reset() -> None:
    """Clear all recorded spans and metrics (tests, between benchmark
    ablation arms).  Does not change whether tracing is enabled."""
    trace.reset()
    metrics.reset()
