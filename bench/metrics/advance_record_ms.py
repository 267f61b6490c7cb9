"""Host time of an ``advance()`` tick in the program's
``serving/advance/record`` span: the carried state, the history, the toggle
count and the plan metrics; ms per tick, from the program's
``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("serving/advance/record", ctx)
