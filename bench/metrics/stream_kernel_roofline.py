"""The streaming kernel's share of its roofline: the least time the chip
needs to move the bytes the spec needs (``bench.spec_bytes``), at the
device's HBM bandwidth, over the kernel's time per call.  The spec needs
no matrix operations, so the byte bound is the roofline."""
from bench.spec_bytes import spec_io_bytes

#: the streaming kernel in the device trace: the cell's only Pallas
#: custom call (the program gives its kernels no name of their own)
KERNELS = {"stream_kernel": "tpu_custom_call"}


def read(ctx):
    run, tr = ctx["run"], ctx["trace"]
    if not tr:
        return None
    s = tr["kernels"].get("stream_kernel", 0.0)
    if s <= 0:
        return None
    per_call = s / run["calls"]
    nbytes = spec_io_bytes(traces=run["traces_per_call"], windows=run["windows"],
                           n_slots=run["n_slots"], n_levels=run["n_levels"])
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / per_call
