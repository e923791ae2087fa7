"""Device time of the Pallas kernels (robust aggregation and agreement)
over the time the device was busy; none without a Pallas kernel."""
from bench.kernel_ops import kernel_time


def read(trace, ctx):
    if not ctx["kernels"]:
        return None
    return 100.0 * kernel_time(trace, ctx["kernels"]) / (
        trace.busy_s * trace.chips)
