"""RFA (Pillutla et al.): the smoothed geometric median by Weiszfeld's
iteration, over bucket means (Karimireddy et al.) of size
floor(alpha_max / alpha), alpha = n_byz / K (the paper's Lemma 3)."""
import jax
import jax.numpy as jnp


def weiszfeld(x, n_iter, nu):
    def step(_, z):
        w = 1.0 / jnp.sqrt(jnp.sum((x - z) ** 2, axis=1) + nu)
        return jnp.sum(w[:, None] * x, axis=0) / jnp.sum(w)
    return jax.lax.fori_loop(0, n_iter, step, jnp.mean(x, axis=0))


def bucket_size(K, n_byz, alpha_max):
    return 1 if n_byz == 0 else max(1, int(alpha_max / (n_byz / K)))


def aggregate(msgs, key, K, n_byz, args):
    bucket = bucket_size(K, n_byz, args["alpha_max"])
    if bucket > 1:
        n_buckets = -(-K // bucket)
        k_perm, _ = jax.random.split(key)
        perm = jax.random.permutation(k_perm, K)
        idx = jnp.concatenate([perm, perm[:n_buckets * bucket - K]])
        msgs = jnp.mean(msgs[idx].reshape(n_buckets, bucket, -1), axis=1)
    return weiszfeld(msgs, args["n_iter"], args["nu"])
