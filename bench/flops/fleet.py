"""Policy-network operations of one fleet iteration, and the kernel
calls it makes, from the configuration's shapes.

An iteration of one fleet (one lane row) runs K agents. Each samples its
trajectories with one forward pass of the policy per step of the
horizon H and differentiates the GPOMDP surrogate over them (one
backward pass, twice the forward's operations, reusing the sampling
forward). A large step (coin 1) uses N trajectories; a small PAGE step
uses B of them, and adds the importance-weighted term at the previous
parameters: one forward for the weight and one forward and backward of
the surrogate. Trajectories sampled and then thrown away by a small
step's weights do not count, nor does anything outside the policy
network (the environment, the aggregation, the agreement).

The kernel calls of a component (aggregator, agreement) are in
``fleet_calls/<namespace>.<name>.py``; a component without that file
calls no kernel.
"""
from __future__ import annotations

import importlib.util
import os

from bench.reference.fleet import component, layer_shapes

CALLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "fleet_calls")


def forward_flops(cfg: dict) -> int:
    """Multiply-adds of one policy forward pass for one observation."""
    env = component("env", cfg["env"])
    return sum(2 * a * b for a, b in layer_shapes(env, cfg["hidden"]))


def n_params(cfg: dict) -> int:
    env = component("env", cfg["env"])
    return sum(b + a * b for a, b in layer_shapes(env, cfg["hidden"]))


def policy_flops(cfg: dict, large_row_iters: int, small_row_iters: int) \
        -> int:
    per_traj = cfg["env_args"]["horizon"] * forward_flops(cfg)
    large = cfg["K"] * cfg["N"] * 3 * per_traj
    small = cfg["K"] * cfg["B"] * (3 + 4) * per_traj
    return large_row_iters * large + small_row_iters * small


def kernel_calls(cfg: dict) -> dict:
    """Kernel -> [(calls per row iteration, shape arguments)], the shape
    as the kernel's ``ops_bytes`` in ``kernels/`` takes it."""
    d = n_params(cfg)
    # the honest diameter, every iteration
    out = {"pairwise_dist": [(1, (cfg["K"], d))]}
    parts = [("aggregator", cfg["aggregator"])]
    if cfg["kappa"] > 0:
        parts.append(("agreement", cfg["agreement"]))
    for namespace, name in parts:
        path = os.path.join(CALLS, f"{namespace}.{name}.py")
        if not os.path.isfile(path):
            continue
        spec = importlib.util.spec_from_file_location(
            f"bench_calls_{namespace}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for kernel, n, shape in mod.calls(cfg, d):
            out.setdefault(kernel, []).append((n, shape))
    return out
