"""Dispatched entry points for the masked-neighbor gossip reduce.

``gossip_reduce`` fuses gather + reduce from a (K, d) message matrix over
the padded ``nbr_idx (K, deg_max)`` table; ``neighbor_reduce`` reduces an
already-gathered (K, deg_max, d) tensor (the per-receiver equivocation
path, where no shared message matrix exists).
"""
from repro.kernels.dispatch import register_kernel
from repro.kernels.gossip_reduce import ref
from repro.kernels.gossip_reduce.gossip_reduce import (
    gossip_reduce_pallas, neighbor_reduce_pallas)

gossip_reduce = register_kernel(
    "gossip_reduce", jnp_impl=ref.gossip_reduce,
    pallas_impl=gossip_reduce_pallas, modes=ref.MODES)

neighbor_reduce = register_kernel(
    "neighbor_reduce", jnp_impl=ref.neighbor_reduce,
    pallas_impl=neighbor_reduce_pallas, modes=ref.MODES)
