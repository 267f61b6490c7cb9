"""Size of the time-varying threshold table the streaming kernel reads, in
MB (10^6 bytes): the program's gauge ``provision/wait_table_bytes``
(K x T x lanes x 4 bytes; 0 where the thresholds are constant), as the
window's last planning call set it."""
from bench import program_spans

program_spans.start()


def read(ctx):
    reg = program_spans._frozen()
    if reg is None or ctx["run"]["kind"] != "plan":
        return None
    nbytes = reg.gauge_value("provision/wait_table_bytes")
    return None if nbytes is None else nbytes / 1e6
