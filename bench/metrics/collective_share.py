"""collective_share: device seconds of collective operations (the
slice-sum's all-reduce) over chips x traced window, in percent.  Read
only where the cell spans more than one chip.

No cell reports it yet: it is for the four-chip cell ``syc30.amp.x4``
(mix ``bench/mixes/amp-x4.json``), which a later benchmark change adds
with one ``workloads`` entry and one ``per_layer`` entry (PERF.md,
Open questions)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["chips"] < 2 or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["op_s"].get("collective", 0.0) / (
        tr["chips"] * tr["window_s"]
    )
