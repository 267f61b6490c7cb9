"""``repro.core.provision(spec)``: the default (scan) route, or with the
traffic's ``mesh`` the program's fleet path."""
from bench.callers import PlanCaller


class Caller(PlanCaller):
    ENTRY = "provision"
