"""Host time of a planning call in the program's ``provision/dispatch`` span:
the engine body's call, from its start to its return (tracing, dispatch; the
device runs on); ms per call, from the program's ``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("provision/dispatch", ctx)
