"""The rfa kernel's share of its roofline in the traced window."""
from bench.kernel_ops import roofline_share


def read(trace, ctx):
    return roofline_share(trace, ctx, "rfa")
