"""Host time of a planning call: from the entry call to its return, before
the caller blocks on the result (``_prepare``, eager ops, dispatch), mean
per call, on the benchmark's host clock."""


def read(ctx):
    run = ctx["run"]
    if run["kind"] != "plan" or not run["host_s"]:
        return None
    return 1e3 * sum(run["host_s"]) / len(run["host_s"])
