"""Memory contracts: per-device footprint bounds on compiled programs.

``tests/test_flat_aggregation.py`` proved once, for one mesh, that the
sharded flat aggregators compile to an O(K² + K·D/devices) per-device
footprint.  This pass turns that one-off assertion into a declarative
contract table checked over CI-faked mesh sizes: for every
(aggregator, K, devices) contract the compiled program's

* ``argument_size_in_bytes`` must stay within one agent-stack *shard*
  (K·D·4 / devices) plus a small fixed slack — the program must never
  gather the full (K, D) stack onto one device;
* ``temp_size_in_bytes`` must stay within ``temp_factor`` × (shard +
  K²·4) — temporaries are a small multiple of one shard plus the K×K
  score/distance matrix.

Faking devices requires ``XLA_FLAGS=--xla_force_host_platform_device_count``
to be set *before* jax initializes, so :func:`run` executes the checks in
a subprocess (``python -m repro.analysis.memcheck``) and parses JSON
findings from its stdout; the in-process entry point is :func:`child_main`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Optional

from repro.analysis.findings import Finding

_AGG_PATH = "src/repro/distributed/aggregation.py"
_MARK = "MEMCHECK_JSON:"


@dataclasses.dataclass(frozen=True)
class MemContract:
    """One compiled-program footprint bound on a faked ``devices``-way mesh.

    Bounds (bytes, per device, f32 stacks):

    * arguments ≤ ``K*D*4 / devices + arg_slack``
    * temporaries ≤ ``temp_factor * (K*D*4 / devices + K*K*4)``
    """
    aggregator: str
    K: int
    devices: int
    arg_slack: int = 4096
    temp_factor: int = 4

    @property
    def name(self) -> str:
        return f"{self.aggregator}(K={self.K})@{self.devices}dev"


def contracts() -> list:
    """The contract table: both CI-faked mesh sizes, both flat-path
    aggregators the seed test covered, plus the K used by the paper-scale
    federated runs (K=8)."""
    out = []
    for devices in (2, 4):
        for agg in ("krum", "rfa"):
            out.append(MemContract(aggregator=agg, K=8, devices=devices))
    return out


# ---------------------------------------------------------------------------
# Child side (runs under the forced-device-count XLA flag)
# ---------------------------------------------------------------------------


def _check_contracts(table) -> list:
    """Evaluate contracts in-process; requires ≥ max devices available.
    Returns findings as plain dicts (JSON-portable)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.base import get_config, reduced
    from repro.core.registry import resolve
    from repro.models.model import init_params

    # reduced-transformer D: the realistic "large model" scale for CI
    shapes = jax.eval_shape(
        lambda k: init_params(reduced(get_config("qwen2.5-3b")), k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    D = int(sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)))

    findings = []
    for c in table:
        if c.devices > len(jax.devices()):
            findings.append(dict(
                rule="mesh-unavailable",
                message=f"[{c.name}] contract needs {c.devices} devices "
                        f"but only {len(jax.devices())} are visible — the "
                        f"memcheck subprocess must force "
                        f"--xla_force_host_platform_device_count"))
            continue
        mesh = Mesh(np.asarray(jax.devices()[:c.devices]), ("model",))
        sh = NamedSharding(mesh, P(None, "model"))
        agg = resolve("aggregator", c.aggregator, K=c.K, n_byz=1,
                      sharded=True)
        f = jax.jit(lambda a, k: agg(a, k), in_shardings=(sh, None),
                    out_shardings=NamedSharding(mesh, P("model")))
        xs = jax.ShapeDtypeStruct((c.K, D), jnp.float32)
        ks = jax.ShapeDtypeStruct((2,), jnp.uint32)
        ma = f.lower(xs, ks).compile().memory_analysis()
        shard = c.K * D * 4 // c.devices
        arg_bound = shard + c.arg_slack
        temp_bound = c.temp_factor * (shard + c.K * c.K * 4)
        if ma.argument_size_in_bytes > arg_bound:
            findings.append(dict(
                rule="argument-footprint",
                message=f"[{c.name}] arguments occupy "
                        f"{ma.argument_size_in_bytes} bytes > bound "
                        f"{arg_bound} (one K·D/devices shard + "
                        f"{c.arg_slack}) — the flat path is gathering the "
                        f"full (K, D) stack instead of staying sharded"))
        if ma.temp_size_in_bytes > temp_bound:
            findings.append(dict(
                rule="temp-footprint",
                message=f"[{c.name}] temporaries occupy "
                        f"{ma.temp_size_in_bytes} bytes > bound "
                        f"{temp_bound} ({c.temp_factor}·(shard + K²·4)) — "
                        f"intermediate buffers exceed "
                        f"O(K² + K·D/devices)"))
    return findings


def child_main() -> int:
    """Entry for the forced-device-count subprocess: print one
    ``MEMCHECK_JSON: [...]`` line and exit 0 (findings are data, not a
    crash)."""
    findings = _check_contracts(contracts())
    print(_MARK + json.dumps(findings))
    return 0


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------


def run(root: Optional[Path] = None, devices: int = 4,
        timeout: int = 1200) -> list:
    """Spawn the forced-device subprocess and lift its JSON findings."""
    from repro.analysis.lint import repo_root
    root = root or repo_root()
    env = dict(os.environ)
    # the child analyses fake CPU devices; pinning the platform keeps it
    # off an accelerator the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    src = str(root / "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.memcheck"],
        env=env, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        return [Finding("memcheck", "subprocess-crash", _AGG_PATH, 0,
                        f"memcheck child exited {proc.returncode}: "
                        f"{proc.stderr[-1000:]}")]
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            raw = json.loads(line[len(_MARK):])
            return [Finding("memcheck", f["rule"], _AGG_PATH, 0,
                            f["message"]) for f in raw]
    return [Finding("memcheck", "subprocess-protocol", _AGG_PATH, 0,
                    "memcheck child produced no MEMCHECK_JSON line: "
                    + proc.stdout[-500:])]


if __name__ == "__main__":
    sys.exit(child_main())
