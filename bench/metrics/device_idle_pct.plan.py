"""Share of the traced planning window in which no operation ran on the
device."""


def read(ctx):
    run, tr = ctx["run"], ctx["trace"]
    if run["kind"] != "plan" or not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
