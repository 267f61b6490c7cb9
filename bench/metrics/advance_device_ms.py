"""Device busy time per ``advance()`` tick, from the trace."""


def read(ctx):
    run, tr = ctx["run"], ctx["trace"]
    if run["kind"] != "live" or not tr or not tr["busy_s"]:
        return None
    return 1e3 * tr["busy_s"] / run["calls"]
