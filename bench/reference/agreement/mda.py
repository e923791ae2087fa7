"""Avg-Agree by minimum-diameter averaging (the paper's Algorithm 3) on
the complete graph: in each of kappa rounds every agent receives all K
vectors, the Byzantine ones as the attack makes them, and takes the mean
of the ceil((1 - alpha_bar) K) <= K - n_byz of them whose largest
pairwise distance is least."""
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np


def min_diameter_average(x, n_keep):
    K = x.shape[0]
    d2 = jnp.sum((x[:, None, :] - x[None, :, :]) ** 2, axis=-1)
    subsets = np.array(list(itertools.combinations(range(K), n_keep)))
    pairs = np.array(list(itertools.combinations(range(n_keep), 2)))
    a, b = subsets[:, pairs[:, 0]], subsets[:, pairs[:, 1]]
    best = jnp.argmin(jnp.max(d2[a, b], axis=1))
    return jnp.mean(x[jnp.asarray(subsets)[best]], axis=0)


def agree(theta, key, kappa, n_byz, byz, attack, args, topology):
    if topology != "complete":
        raise ValueError("this reference agrees on the complete graph only")
    K = theta.shape[0]
    n_keep = max(1, min(math.ceil((1 - args["alpha_bar"]) * K), K - n_byz))
    for k in jax.random.split(key, kappa):
        avg = min_diameter_average(attack(theta, byz, k), n_keep)
        theta = jnp.broadcast_to(avg, theta.shape)
    return theta
