"""device_idle_share: 1 - (union of the device's operation intervals) /
(traced window), averaged over the chips used, in percent."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["busy_s"] or tr["window_s"] <= 0:
        return None
    busy = sum(tr["busy_s"]) / len(tr["busy_s"])
    return 100.0 * (1.0 - busy / tr["window_s"])
