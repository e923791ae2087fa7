"""LargeNoise (the paper's Section 6): every Byzantine agent sends
Gaussian noise of scale ``sigma`` in place of its message."""
import jax
import jax.numpy as jnp


def attack(msgs, byz, key, args):
    noise = args["sigma"] * jax.random.normal(key, msgs.shape, jnp.float32)
    return jnp.where(byz[:, None], noise.astype(msgs.dtype), msgs)
