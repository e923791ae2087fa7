"""Dispatched entry point for the coordinate-wise trimmed mean.

Backend selection (pallas / pallas-interpret / jnp) lives in
``repro.kernels.dispatch``; override per call with ``backend=`` or globally
via ``REPRO_KERNEL_BACKEND``.
"""
from repro.kernels.dispatch import register_kernel
from repro.kernels.trimmed_mean import ref
from repro.kernels.trimmed_mean.trimmed_mean import trimmed_mean_pallas

trimmed_mean = register_kernel(
    "trimmed_mean", jnp_impl=ref.trimmed_mean,
    pallas_impl=trimmed_mean_pallas)
