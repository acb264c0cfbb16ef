"""transpose_share: device seconds of transposes and copies (the data
formatting between contraction steps, see ``bench.trace_reduce.kind``)
over the device's busy seconds in the traced window, in percent."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or sum(tr["busy_s"]) <= 0:
        return None
    return 100.0 * tr["op_s"].get("transpose", 0.0) / sum(tr["busy_s"])
