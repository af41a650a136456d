"""NetFlix-shaped bipartite ratings graph, on the device.

``ratings`` (user, item, rating) triples; vertices ``0 .. users - 1`` are
users and ``users .. users + items - 1`` items, and every rating is an
edge in both directions (user -> item and item -> user) weighted by the
rating, the layout Lux's collaborative filtering reads. User activity and
item popularity follow ``floor(n * z ** exponent)`` for a uniform ``z``,
so the first index is the busiest; user and item labels are then permuted
at random. Ratings are drawn from ``rating_probs`` (probabilities of
1 .. len(rating_probs)).

Config keys: ``users``, ``items``, ``ratings``, ``rating_probs``,
``user_exponent``, ``item_exponent``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.generators import HostGraph, csc_arrays, seed_key, to_host


def skewed_index(key, n: int, count: int, exponent: float):
    """``count`` indices in [0, n): ``floor(n * z ** exponent)``."""
    z = jax.random.uniform(key, (count,), jnp.float32)
    idx = jnp.floor(n * z ** exponent).astype(jnp.int32)
    return jnp.clip(idx, 0, n - 1)


def rating_triples(key, users: int, items: int, ratings: int, probs,
                   user_exponent: float, item_exponent: float):
    """int32 (user, item, rating) before labels are permuted."""
    ku, ki, kr = jax.random.split(key, 3)
    u = skewed_index(ku, users, ratings, user_exponent)
    i = skewed_index(ki, items, ratings, item_exponent)
    logits = jnp.log(jnp.asarray(probs, jnp.float32))
    r = jax.random.categorical(kr, logits, shape=(ratings,)).astype(
        jnp.int32) + 1
    return u, i, r


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _generate(key, users: int, items: int, ratings: int, probs: tuple,
              user_exponent: float, item_exponent: float):
    k_triples, k_pu, k_pi = jax.random.split(key, 3)
    u, i, r = rating_triples(k_triples, users, items, ratings, probs,
                             user_exponent, item_exponent)
    u = jax.random.permutation(k_pu, users).astype(jnp.int32)[u]
    i = jax.random.permutation(k_pi, items).astype(jnp.int32)[i] + users
    src = jnp.concatenate([u, i])
    dst = jnp.concatenate([i, u])
    w = jnp.concatenate([r, r])
    return csc_arrays(src, dst, users + items, w)


def generate(config: dict, seed: int) -> HostGraph:
    users, items = int(config["users"]), int(config["items"])
    row_ptr, col_src, w = _generate(
        seed_key(seed), users, items, int(config["ratings"]),
        tuple(float(p) for p in config["rating_probs"]),
        float(config["user_exponent"]), float(config["item_exponent"]))
    return to_host(users + items, row_ptr, col_src, w)
