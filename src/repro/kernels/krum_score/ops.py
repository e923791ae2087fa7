"""Dispatched entry point for Krum neighbor scoring."""
from repro.kernels.dispatch import register_kernel
from repro.kernels.krum_score import ref
from repro.kernels.krum_score.krum_score import krum_scores_pallas

krum_scores = register_kernel(
    "krum_score", jnp_impl=ref.krum_scores, pallas_impl=krum_scores_pallas)
