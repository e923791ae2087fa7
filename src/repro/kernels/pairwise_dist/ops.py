"""Dispatched entry point for pairwise squared distances.

Backend selection (pallas / pallas-interpret / jnp) lives in
``repro.kernels.dispatch``; the Pallas body is validated in interpret mode
by the kernel test sweep.
"""
from repro.kernels.dispatch import register_kernel
from repro.kernels.pairwise_dist import ref
from repro.kernels.pairwise_dist.pairwise_dist import pairwise_sq_dists_pallas

pairwise_sq_dists = register_kernel(
    "pairwise_dist", jnp_impl=ref.pairwise_sq_dists,
    pallas_impl=pairwise_sq_dists_pallas)
