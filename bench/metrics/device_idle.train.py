"""device_idle.train: the share of the traced window of train steps in
which no operation ran on the device (1 - union of op intervals / window),
averaged over the chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s") or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
