"""Time of the streaming Pallas kernel per call, from its events in the
device trace, found by the kernel's name."""

#: the streaming kernel in the device trace: the cell's only Pallas
#: custom call (the program gives its kernels no name of their own)
KERNELS = {"stream_kernel": "tpu_custom_call"}


def read(ctx):
    run, tr = ctx["run"], ctx["trace"]
    if not tr:
        return None
    s = tr["kernels"].get("stream_kernel", 0.0)
    return 1e3 * s / run["calls"] if s > 0 else None
