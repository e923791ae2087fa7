"""Plain averaging of all K messages (the naive baseline)."""
import jax.numpy as jnp


def aggregate(msgs, key, K, n_byz, args):
    return jnp.mean(msgs, axis=0)
