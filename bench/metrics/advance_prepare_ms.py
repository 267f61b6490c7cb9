"""Host time of an ``advance()`` tick in the program's
``serving/advance/prepare`` span: validation, the chunk's upload and
padding, the per-level Delta broadcast, the stepper's first state; ms per
tick, from the program's ``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("serving/advance/prepare", ctx)
