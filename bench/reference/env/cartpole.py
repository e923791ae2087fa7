"""CartPole (Barto, Sutton and Anderson 1983; the classic-control
constants): reward 1 per step while the pole stands."""
import math

import jax
import jax.numpy as jnp

OBS_DIM, N_ACTIONS = 4, 2
GRAVITY, M_CART, M_POLE, HALF_LEN, FORCE, TAU = 9.8, 1.0, 0.1, 0.5, 10.0, 0.02
X_LIMIT, THETA_LIMIT = 2.4, 12 * math.pi / 180


def reset(key, dtype):
    return jax.random.uniform(key, (OBS_DIM,), jnp.float32,
                              -0.05, 0.05).astype(dtype)


def step(s, action):
    """(next state, reward, done)."""
    x, x_dot, th, th_dot = s[0], s[1], s[2], s[3]
    force = jnp.where(action == 1, FORCE, -FORCE).astype(s.dtype)
    cos, sin = jnp.cos(th), jnp.sin(th)
    total = M_CART + M_POLE
    temp = (force + M_POLE * HALF_LEN * th_dot ** 2 * sin) / total
    th_acc = (GRAVITY * sin - cos * temp) / (
        HALF_LEN * (4.0 / 3.0 - M_POLE * cos ** 2 / total))
    x_acc = temp - M_POLE * HALF_LEN * th_acc * cos / total
    s2 = jnp.stack([x + TAU * x_dot, x_dot + TAU * x_acc,
                    th + TAU * th_dot, th_dot + TAU * th_acc])
    done = (jnp.abs(s2[0]) > X_LIMIT) | (jnp.abs(s2[2]) > THETA_LIMIT)
    return s2, jnp.ones((), s.dtype), done
