"""Host time of a planning call in the program's ``provision/finish`` span: the
squeezes back to the spec's axes, the per-level and fleet cost sums and the
result; ms per call, from the program's ``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("provision/finish", ctx)
