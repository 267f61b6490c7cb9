"""Host time of a planning call in the program's ``provision/prepare`` span:
``_prepare``: the demand's upload and shape checks, the per-level cost
broadcasts, the windows array and the keys; ms per call, from the program's
``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("provision/prepare", ctx)
