"""``repro.serving.FleetProvisioner.advance(chunk)``: the live stepper."""
from bench.callers import LiveCaller as Caller  # noqa: F401
