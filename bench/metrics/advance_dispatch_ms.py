"""Host time of an ``advance()`` tick in the program's
``serving/advance/dispatch`` span: the ``stepper_chunk`` call (and the
deferral scans, with a deferral spec); ms per tick, from the program's
``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("serving/advance/dispatch", ctx)
