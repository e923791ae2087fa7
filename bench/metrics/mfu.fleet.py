"""Policy-network operations the fleet's iterations in the traced window
need (``bench/flops/fleet.py``), over the window times the chips' peak."""


def read(trace, ctx):
    peak = ctx["peak"]["flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["policy_flops"] / (trace.window_s * peak)
