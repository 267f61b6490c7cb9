"""``repro.core.provision_stream(spec)``: with the traffic's ``mesh`` the
streaming Pallas kernel, without it the chunked scan route."""
from bench.callers import PlanCaller


class Caller(PlanCaller):
    ENTRY = "provision_stream"
