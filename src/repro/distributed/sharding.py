"""Sharding rules for the (pod, data, model) production mesh.

Parameters are named-sharded by leaf-path rules operating on *trailing*
dimensions, so the same table serves plain trees, layer-stacked trees
(leading L), and federated agent-stacked trees (leading K).

Federation mapping (DESIGN.md §3):
  fed_axis="data": agents live on every (pod, data) rank -> K = pods*data;
                   per-agent batch is unsharded (local).
  fed_axis="pod" : one agent per pod -> K = pods; the data axis shards the
                   agent's batch (and could FSDP params; we keep params
                   model-sharded + data-replicated, optimizer state too).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# Lane mesh: 1-D data-parallel layout for the engine's scenario sweeps
# ---------------------------------------------------------------------------


#: lane-mesh override stack (see :func:`use_lane_mesh`); the top entry —
#: which may be None, meaning "no sharding" — replaces the default
#: local-devices mesh everywhere the engine asks for one.
_LANE_MESH: list = []


@contextlib.contextmanager
def use_lane_mesh(mesh: Optional[Mesh]):
    """Install ``mesh`` as the engine's lane mesh for the dynamic extent
    of the context — how the sweep service points the whole lane-batching
    stack (init/window/one-shot programs, padding) at a process-spanning
    mesh without threading a mesh argument through every layer.  Passing
    None disables lane sharding entirely."""
    _LANE_MESH.append(mesh)
    try:
        yield mesh
    finally:
        _LANE_MESH.pop()


def lane_mesh(devices=None, spanning: bool = False) -> Optional[Mesh]:
    """1-D ``("lane",)`` mesh over the local devices, used by the engine
    to spread a flattened lane×seed scenario batch (DESIGN.md §2).
    Returns None on a single device — the identity layout, so CPU tests
    and single-chip runs skip sharding entirely.

    ``spanning=True`` builds the mesh over **all** processes' devices
    (``jax.devices()``), the process-spanning layout the sweep service
    uses after :func:`init_distributed`: every process dispatches the
    same program and XLA moves each row's work to the process owning its
    shard (DESIGN.md §12)."""
    if devices is None:
        if _LANE_MESH:
            return _LANE_MESH[-1]
        devices = jax.devices() if spanning else jax.local_devices()
    devs = list(devices)
    if len(devs) <= 1:
        return None
    return Mesh(np.asarray(devs), ("lane",))


def lane_sharding(mesh: Optional[Mesh], n_rows: int) \
        -> Optional[NamedSharding]:
    """NamedSharding splitting a leading batch axis of size ``n_rows``
    over the lane mesh; None (replicate — the identity layout) without a
    mesh or when the batch does not divide the device count evenly (the
    engine pads batches to :func:`padded_rows` precisely so this keeps
    dividing)."""
    if mesh is None or n_rows % mesh.size != 0:
        return None
    return NamedSharding(mesh, P("lane"))


def spans_processes(mesh: Optional[Mesh]) -> bool:
    """True when the mesh holds devices from more than one process."""
    if mesh is None:
        return False
    return len({d.process_index for d in mesh.devices.flat}) > 1


def padded_rows(mesh: Optional[Mesh], n_rows: int) -> int:
    """Rows the engine runs for a flattened lane×seed batch of
    ``n_rows``: ``n_rows`` itself without a mesh; on a lane mesh the
    smallest multiple of the device count ≥ ``n_rows`` that gives every
    device at least two rows.  The engine pads the batch to this size
    with duplicate rows — sliced off before summaries — so uneven batches
    shard over the mesh instead of falling back to the identity layout.
    At least two, because a device holding a single row runs the program
    with a unit batch axis, which XLA compiles to different arithmetic:
    its rows would then differ in the last bits from the same rows run
    together on one device."""
    if mesh is None:
        return n_rows
    return max(2, -(-n_rows // mesh.size)) * mesh.size


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int) -> None:
    """Bring up the cross-process runtime for a spanning lane mesh.

    On the CPU backend jax's cross-process collectives need the gloo
    transport, and the flag must land **before** the backend
    initializes — ``jax.distributed.initialize`` alone leaves the
    default in place and the first spanning dispatch fails with
    "Multiprocess computations aren't implemented on the CPU backend".
    No-op for a single process."""
    if num_processes <= 1:
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def global_rows(mesh: Mesh, arr) -> jax.Array:
    """Assemble a process-spanning global array from a host-local copy
    of the full ``(R, ...)`` batch: every process holds the same host
    value (sweep operands are derived deterministically from the grid)
    and contributes the shards of the rows its devices own."""
    sharding = NamedSharding(mesh, P("lane"))
    arr = np.asarray(arr)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def host_assignment(costs, n_hosts: int) -> list:
    """Greedy longest-processing-time schedule: ``assign[i]`` is the
    host owning group ``i``, balancing summed cost per host.  The
    work-partitioning fallback when processes cannot form a spanning
    mesh — uneven lane groups land on the least-loaded host (ties to
    the lowest rank) so no process idles while another drains a long
    tail."""
    costs = [float(c) for c in costs]
    order = sorted(range(len(costs)), key=lambda i: (-costs[i], i))
    loads = [0.0] * max(int(n_hosts), 1)
    assign = [0] * len(costs)
    for i in order:
        h = min(range(len(loads)), key=lambda j: (loads[j], j))
        assign[i] = h
        loads[h] += costs[i]
    return assign

# leaf name -> trailing dim that gets the "model" axis
_MODEL_LAST = {"wq", "wk", "wv", "w_gate", "w_up", "bq", "bk", "bv",
               "w_uq", "w_uk", "w_uv", "lm_head"}
_MODEL_SECOND = {"wo", "w_down"}
_REPLICATE = {"router", "norm_attn", "norm_mlp", "final_norm", "norm_m",
              "norm_s", "frontend_proj", "w_dq", "w_dkv"}


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def fed_axes(cfg: ModelConfig, mesh: Mesh) -> Tuple[str, ...]:
    has_pod = "pod" in mesh.shape
    if cfg.fed_axis == "pod":
        return ("pod",) if has_pod else ()
    if cfg.fed_axis == "all":
        # TP-free federation: one agent per chip (beyond-paper sharding,
        # EXPERIMENTS.md §Perf) — no tensor parallelism, the only
        # collectives left are the paper's aggregation + agreement.
        return ("pod", "data", "model") if has_pod else ("data", "model")
    return ("pod", "data") if has_pod else ("data",)


def n_agents(cfg: ModelConfig, mesh: Mesh) -> int:
    axes = fed_axes(cfg, mesh)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return max(n, 1)


def batch_axes(cfg: ModelConfig, mesh: Mesh) -> Tuple[str, ...]:
    """Axes sharding the per-agent batch dimension."""
    if cfg.fed_axis == "pod":
        return ("data",)
    if getattr(cfg, "intra_agent_dp", False) and cfg.fed_axis == "data":
        return ("model",)
    return ()


def _path_names(path) -> list:
    names = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            names.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            names.append(p.name)
    return names


def param_spec(cfg: ModelConfig, path, leaf, mesh: Mesh,
               stacked: bool = False) -> P:
    """PartitionSpec for one parameter leaf."""
    names = _path_names(path)
    name = names[-1] if names else ""
    ndim = leaf.ndim
    spec = [None] * ndim
    if cfg.fed_axis == "all" or getattr(cfg, "intra_agent_dp", False):
        # agent params replicated within the agent's chip group (TP-free)
        if stacked:
            axes = fed_axes(cfg, mesh)
            spec[0] = axes if axes else None
        return P(*spec)
    model_ok = mesh_axis_size(mesh, "model") > 1

    in_recurrent = (cfg.family == "ssm") or ("ssm" in names) \
        or ("m" in names) or ("s" in names)
    e = cfg.moe.n_experts if cfg.moe is not None else 0
    expert_leaf = (cfg.moe is not None and "mlp" in names
                   and name in ("w_gate", "w_up", "w_down")
                   and "shared" not in names)

    msize = mesh_axis_size(mesh, "model")

    def put(dim, axis="model", size=None):
        if leaf.shape[dim] % (size or msize) == 0:
            spec[dim] = axis

    if model_ok and not in_recurrent and name not in _REPLICATE:
        if name == "embed":
            put(-2)                     # vocab-parallel
        elif expert_leaf and e % msize == 0:
            put(-3)                     # expert-parallel
        elif name in _MODEL_LAST:
            put(-1)
        elif name in _MODEL_SECOND:
            put(-2)
    # FSDP-over-layers: shard the layer-stack dim over "data" so the layer
    # scan gathers one layer's weights at a time (fed_axis="pod" archs that
    # would not otherwise fit, e.g. grok-1-314b).
    dsize = mesh_axis_size(mesh, "data")
    if (getattr(cfg, "fsdp_layers", False) and names
            and names[0] == "blocks" and dsize > 1):
        ldim = 1 if stacked else 0
        if ldim < ndim and spec[ldim] is None \
                and leaf.shape[ldim] % dsize == 0:
            spec[ldim] = "data"
    if stacked:        # leaf already carries the leading K dim
        axes = fed_axes(cfg, mesh)
        spec[0] = axes if axes else None
    return P(*spec)


def param_shardings(cfg: ModelConfig, params_shape, mesh: Mesh,
                    stacked: bool = False):
    """Tree of NamedShardings matching a params(-shaped) tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, param_spec(cfg, path, leaf, mesh, stacked)),
        params_shape)


def batch_spec(cfg: ModelConfig, mesh: Mesh, stacked: bool = True) -> P:
    """Spec for token batches: (K, b, S) if stacked else (B, S)."""
    fa = fed_axes(cfg, mesh)
    ba = batch_axes(cfg, mesh)
    if stacked:
        return P(fa if fa else None, ba if ba else None)
    # serving: batch over every non-model axis
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return P(axes if axes else None)


def cache_shardings(cfg: ModelConfig, cache_shape, mesh: Mesh):
    """KV/state caches: batch dim over (pod, data); heads/features over
    model where the layout allows."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    model_ok = mesh_axis_size(mesh, "model") > 1

    def spec(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        if name in ("pos", "slot_pos"):
            return NamedSharding(mesh, P())
        nd = leaf.ndim
        msize = mesh_axis_size(mesh, "model")
        bsize = 1
        for a in axes:
            bsize *= mesh_axis_size(mesh, a)
        s = [None] * nd
        # leading dims: (L, B, ...) for block caches
        if nd >= 2 and leaf.shape[1] % max(bsize, 1) == 0:
            s[1] = axes if axes else None
        if model_ok:
            if name in ("k", "v") and nd == 5:      # (L,B,W,Hkv,hd)
                if cfg.n_kv_heads % msize == 0:
                    s[3] = "model"
                elif leaf.shape[2] % msize == 0:
                    s[2] = "model"                  # sequence-sharded cache
            elif name in ("c", "k_rope") and nd == 4:   # MLA latent (L,B,W,r)
                if leaf.shape[2] % msize == 0:
                    s[2] = "model"
        return NamedSharding(mesh, P(*s))

    return jax.tree_util.tree_map_with_path(spec, cache_shape)


def shard_hint(x, mesh: Mesh, spec: P):
    """with_sharding_constraint that is a no-op without a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except (ValueError, RuntimeError):
        return x


def ctx_mesh():
    """The mesh installed via jax.set_mesh (None outside a mesh context)."""
    try:
        m = jax.sharding.get_abstract_mesh()
        return m if m and m.shape else None
    except Exception:
        return None


def maybe_shard(x, *spec):
    """with_sharding_constraint that no-ops outside a mesh context, so
    model code can pin layouts for the production mesh without breaking
    CPU tests."""
    m = ctx_mesh()
    if m is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError, TypeError):
        return x
