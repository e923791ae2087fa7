"""Fleet cells: the engine's lane window program, driven as the sweep
service drives it (``repro.sweep.SweepRunner`` with persistence off).

Set-up builds the lane init and window programs for the cell's one
scenario, starts ``rows`` fleets from seeds drawn from the run's seed,
and advances them through the first window with the window's own
call: that compiles and warms every program the window uses, and that
window's outputs are what the reference is compared with. The measured window then keeps calling the same program on the
same carry, one window of ``window_iters`` iterations per call, and
blocks on each as the sweep service does. A run of ``run_iters``
iterations is followed by a fresh one from new seeds, as in a sweep.
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import fleet as fleet_flops
from bench.kernel_ops import classify_hlo
from bench.reference import fleet as reference
from repro.core import engine
from repro.rl.envs import make_env

#: window calls of set-up whose outputs the reference is compared with;
#: the first window holds every number compared (PERF.md section 2)
CHECK_WINDOWS = 1


def spec(name: str, args: dict) -> str:
    """A component as the system's spec string: ``name(k=v,...)``."""
    return f"{name}({','.join(f'{k}={v!r}' for k, v in args.items())})"


def program_spec(cfg: dict) -> dict:
    """The configuration file as the system's DecByzPG config fields;
    ``program`` holds any further field as the system takes it."""
    fields = ("K", "n_byz", "N", "B", "eta", "gamma", "activation",
              "estimator", "optimizer", "kappa", "topology")
    return dict(
        {k: cfg[k] for k in fields}, hidden=tuple(cfg["hidden"]),
        **{ns: spec(cfg[ns], cfg[ns + "_args"])
           for ns in ("attack", "aggregator", "agreement")},
        **cfg.get("program", {}))


class Driver:
    """One fleet cell in one process."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg = config
        self.rows = int(traffic["rows"])
        self.W = int(traffic["window_iters"])
        self.T = int(traffic["run_iters"])
        self.n_check = CHECK_WINDOWS
        if self.T % self.W or self.n_check * self.W > self.T:
            raise ValueError("run_iters must be a multiple of window_iters "
                             "and hold the check windows")
        self.rng = np.random.default_rng(seed % 2 ** 64)
        self.spans = _no_span

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        cfg = self.cfg
        env = make_env(spec(cfg["env"], cfg["env_args"]))
        grid = engine.ScenarioGrid(seeds=tuple(range(self.rows)), axes={})
        _, scenarios = engine.grid_scenarios(grid, algo=cfg["algo"],
                                             base=program_spec(cfg))
        groups = engine.lane_groups(scenarios, algo=cfg["algo"])
        if len(groups) != 1:
            raise ValueError("a fleet cell runs one lane group")
        (static_cfg, names), self.members = next(iter(groups.items()))
        self.init = engine.lane_init_loop(env, static_cfg, self.rows,
                                          cfg["algo"])
        self.win = engine.lane_window_loop(env, static_cfg, self.T, names,
                                           self.W, self.rows, cfg["algo"])
        self._start_run()
        self.first_seeds = self.seeds_host.copy()
        theta0 = np.asarray(self.carry[0])
        chunks, thetas, adam = [], [], None
        for w in range(self.n_check):
            chunk = self._advance()
            chunks.append(jax.tree.map(np.asarray, chunk))
            thetas.append(np.asarray(self.carry[0]))
            if w == 0:
                adam = np.asarray(self.carry[2].m)
        self.early = {
            "returns": np.concatenate([c["returns"] for c in chunks], 1),
            "coins": np.concatenate([c["coins"] for c in chunks], 1),
            "theta0": theta0, "theta": np.stack(thetas, 1), "adam_m": adam}

    def _start_run(self) -> None:
        self.seeds_host = self.rng.integers(0, 2 ** 31 - 1, self.rows,
                                            dtype=np.int64).astype(np.int32)
        self.vals, self.seeds = engine.lane_operands(
            self.members, jnp.asarray(self.seeds_host), self.rows)
        self.carry = self.init(self.seeds)
        self.t = 0

    def _advance(self):
        """One window call on the carry, blocking as the sweep does."""
        with self.spans("bench.host_prep"):
            if self.t == self.T:
                self._start_run()
            ts = np.arange(self.t, self.t + self.W)
        with self.spans("bench.dispatch"):
            out = self.win(self.carry, self.vals, self.seeds, ts)
        with self.spans("bench.readback"):
            self.carry, chunk = jax.block_until_ready(out)
        self.t += self.W
        return chunk

    # -- the measured window -------------------------------------------------

    def measure(self, seconds: float, spans=None) -> dict:
        self.spans = spans or _no_span
        window_s, coins, returns = [], [], []
        start = last = time.perf_counter()
        while not window_s or last - start < seconds:
            chunk = self._advance()
            now = time.perf_counter()
            window_s.append(now - last)
            last = now
            coins.append(chunk["coins"])
            returns.append(chunk["returns"])
        self.spans = _no_span
        finite = [bool(np.isfinite(np.asarray(r)).all()) for r in returns]
        n_large = int(sum(np.asarray(c).sum() for c in coins))
        n_iters = len(window_s) * self.W * self.rows
        return {"elapsed_s": last - start, "window_s": window_s,
                "attempted": len(window_s), "failed": finite.count(False),
                "row_iters": n_iters, "large_iters": n_large}

    def end_to_end(self, stats: dict) -> dict:
        return {"fleet_iters_per_s": stats["row_iters"] / stats["elapsed_s"]}

    def layer_context(self, stats: dict) -> dict:
        """What the per-layer readers need besides the trace."""
        cfg = self.cfg
        return {
            "policy_flops": fleet_flops.policy_flops(
                cfg, stats["large_iters"],
                stats["row_iters"] - stats["large_iters"]),
            "kernel_calls": {
                name: [(n * stats["row_iters"], shape)
                       for n, shape in calls]
                for name, calls in fleet_flops.kernel_calls(cfg).items()},
        }

    def kernel_instructions(self) -> dict:
        """HLO instruction name -> kernel, for each Pallas call in the
        compiled window program (the trace names device ops by their
        instruction)."""
        ts = np.arange(self.W)
        text = self.win.lower(self.carry, self.vals, self.seeds,
                              ts).compile().as_text()
        return classify_hlo(text)

    def free(self) -> None:
        for x in jax.tree.leaves((self.carry, self.vals, self.seeds)):
            x.delete()
        self.carry = self.vals = self.seeds = None

    # -- correctness ---------------------------------------------------------

    def reference_rows(self, dtype=jnp.float32, fault=None) -> dict:
        ref = reference.Fleet(self.cfg, self.T, dtype=dtype, fault=fault)
        return ref.run(self.first_seeds, self.n_check, self.W)

    def compare(self, ref: dict) -> dict:
        return compare(self.early, ref, self.cfg)


def _leaf_norms(x, slices):
    """(..., d) -> (..., n_leaves) Euclidean norm of each leaf."""
    return np.stack([np.linalg.norm(x[..., a:b].astype(np.float64), axis=-1)
                     for _, a, b in slices], -1)


def _norm_gap(got, want, slices) -> float:
    """Worst leaf's gap between the two norms, against the reference
    leaf's norm or the median leaf's, whichever is larger."""
    g, w = _leaf_norms(got, slices), _leaf_norms(want, slices)
    floor = np.median(w, axis=-1, keepdims=True)
    return float(np.max(np.abs(g - w) / np.maximum(w, floor)))


def return_gaps(prog: dict, ref: dict) -> np.ndarray:
    """(rows, iterations) gap of the honest mean return, relative to the
    reference's."""
    r_p, r_r = prog["returns"].astype(np.float64), ref["returns"]
    return np.abs(r_p - r_r) / np.maximum(np.abs(r_r), 1.0)


def compare(prog: dict, ref: dict, cfg: dict) -> dict:
    """Every number the fleet's correctness is judged by, and those
    read only to explain it."""
    slices = reference.leaf_slices(cfg)
    gaps = return_gaps(prog, ref)
    W = gaps.shape[1] // prog["theta"].shape[1]
    d_theta = (np.linalg.norm(prog["theta"][:, 0] - ref["theta"][:, 0],
                              axis=-1)
               / np.linalg.norm(ref["theta"][:, 0], axis=-1))
    steps = np.mean(gaps[:, :3], axis=0)
    return {
        "coin_mismatch": float(np.sum(prog["coins"] != ref["coins"])),
        **{f"return_gap_step{t + 1}": float(steps[t]) for t in range(3)},
        "change_gap_w0": _norm_gap(prog["theta"][:, 0] - prog["theta0"],
                                   ref["theta"][:, 0] - ref["theta0"],
                                   slices),
        # read to explain the limits, not compared (PERF.md section 2)
        "return_gap_w0": float(np.mean(gaps[:, :W])),
        "theta_gap_w0": float(np.max(d_theta)),
        "adam_m_gap_w0": _norm_gap(prog["adam_m"], ref["adam_m"][:, 0],
                                   slices),
    }


def _no_span(name):
    return contextlib.nullcontext()
