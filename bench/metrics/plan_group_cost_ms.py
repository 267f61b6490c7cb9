"""Host time of a planning call in the program's
``provision/finish/group_cost`` span, opened for typed fleets only:
``CostModel.group_reduce`` of ``level_cost`` into the per-type totals (d
eager slices and sums, one stack, the group check); ms per call, from the
program's ``perf_counter``."""
from bench import program_spans

program_spans.start()


def read(ctx):
    return program_spans.ms_per_call("provision/finish/group_cost", ctx)
