"""Host time of an ``advance()`` tick in the program's
``serving/advance/fetch`` span: the plan's copy to the host (and the
backlog's, with a deferral spec); ms per tick, from the program's
``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("serving/advance/fetch", ctx)
