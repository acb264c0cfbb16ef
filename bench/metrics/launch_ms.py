"""launch_ms: the median host duration, in milliseconds, of the
program's ``engine.run_slices`` span over the calls of the traced
window: the ids and mask onto the device (``engine.ids_put``) and the
jitted call up to its return (``engine.launch``), not the device's
work (``repro.engine.session.ContractionSession.run_slices``).

The span reaches the trace as a profiler annotation when the program's
tracing is on (``bench/trace_scopes.py`` turns it on for the traced
window; the harness does not yet, PERF.md, Open questions).  A program
without the span gives nothing."""

import statistics


def read(ctx):
    spans = ((ctx["trace"] or {}).get("program_spans") or {}).get(
        "engine.run_slices"
    )
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
