"""Dispatched entry point for RFA (smoothed-Weiszfeld geometric median)."""
from repro.kernels.dispatch import register_kernel
from repro.kernels.rfa import ref
from repro.kernels.rfa.rfa import rfa_pallas

rfa = register_kernel("rfa", jnp_impl=ref.rfa, pallas_impl=rfa_pallas)
