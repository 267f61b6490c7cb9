"""Device time of a planning call: the union of the device's operation
intervals over the traced window, per call (calls do not overlap: each one
blocks on its result before the next starts)."""


def read(ctx):
    run, tr = ctx["run"], ctx["trace"]
    if run["kind"] != "plan" or not tr or not tr["busy_s"]:
        return None
    return 1e3 * tr["busy_s"] / run["calls"]
