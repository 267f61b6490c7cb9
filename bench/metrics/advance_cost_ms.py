"""Host time of an ``advance()`` tick in the program's ``serving/advance/cost``
span: the per-level and fleet cost operations and the ``last_plan`` result;
ms per tick, from the program's ``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("serving/advance/cost", ctx)
