"""Host time per ``advance()`` tick: the caller's wall time of the tick not
covered by device busy time, mean per tick."""


def read(ctx):
    run, tr = ctx["run"], ctx["trace"]
    if run["kind"] != "live" or not tr or not run["host_s"]:
        return None
    wall = sum(run["host_s"])
    return 1e3 * max(wall - tr["busy_s"], 0.0) / run["calls"]
