"""Seeded vector collections, made on the device.

The mixture is the one `repro.data.vectors.make_dataset(intrinsic_dim=r)`
draws on the host: `components` Gaussian centres in an r-dim latent space
(scale `center_scale`), points spread around them by `cluster_std`, lifted
into the ambient dimension through a random orthonormal map plus isotropic
noise of std `lift_noise`. The learn set is diversified as there (a fifth
of it noise-perturbed, a tenth from unseen modes) so the recall predictor
sees hard queries; the query pool is drawn from the base distribution.

Each array is one jitted call on the device from the run's seed: set-up
does no host-side generation.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Collection(NamedTuple):
    base: jax.Array     # f32[n, d]
    learn: jax.Array    # f32[learn_queries, d]
    pool: jax.Array     # f32[pool_queries, d]


def key_for(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (64-bit seeds too)
    and a stream number."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


_STATIC = ("n", "d", "r", "components", "center_scale", "cluster_std",
           "lift_noise", "far")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _draw(shared, own, n: int, d: int, r: int, components: int,
          center_scale: float, cluster_std: float, lift_noise: float,
          far: bool = False) -> jax.Array:
    """n points of the mixture; with far=True, of unseen modes of it.
    `shared` fixes the centres and the lift, `own` the points."""
    k_c, k_b = jax.random.split(shared)
    centers = jax.random.normal(k_c, (components, r)) * center_scale
    basis = jnp.linalg.qr(jax.random.normal(k_b, (d, r)))[0].T      # [r, d]
    k_a, k_p, k_n = jax.random.split(own, 3)
    if far:
        mu = jax.random.normal(k_a, (n, r)) * center_scale
    else:
        mu = centers[jax.random.randint(k_a, (n,), 0, components)]
    z = mu + jax.random.normal(k_p, (n, r)) * cluster_std
    return z @ basis + jax.random.normal(k_n, (n, d)) * lift_noise


@jax.jit
def _perturb(key, q: jax.Array) -> jax.Array:
    """The learn set's noisy share: sigma^2 = pct * ||q|| / d with pct
    uniform in [0.5, 8] per query (make_dataset's diversification)."""
    k_pct, k_eps = jax.random.split(key)
    pct = jax.random.uniform(k_pct, (q.shape[0], 1), minval=0.5, maxval=8.0)
    sigma = jnp.sqrt(pct * jnp.linalg.norm(q, axis=1, keepdims=True)
                     / q.shape[1])
    return q + jax.random.normal(k_eps, q.shape) * sigma


def generate(cfg: dict, seed: int) -> Collection:
    """Base, learn set and query pool of configuration `cfg` from `seed`."""
    dcfg = cfg["data"]
    shape = dict(d=cfg["dim"], r=dcfg["intrinsic_dim"],
                 components=dcfg["components"],
                 center_scale=dcfg["center_scale"],
                 cluster_std=dcfg["cluster_std"],
                 lift_noise=dcfg["lift_noise"])
    shared = key_for(seed, 0)

    def draw(stream: int, n: int, far: bool = False) -> jax.Array:
        return _draw(shared, key_for(seed, stream), n=n, far=far, **shape)

    base = draw(1, cfg["n"])
    nl = cfg["fit"]["learn_queries"]
    learn = draw(2, nl)
    n_noisy, n_far = nl // 5, nl // 10
    if n_noisy:
        learn = learn.at[:n_noisy].set(
            _perturb(key_for(seed, 3), learn[:n_noisy]))
    if n_far:
        learn = learn.at[n_noisy:n_noisy + n_far].set(
            draw(4, n_far, far=True))
    pool = draw(5, cfg["pool_queries"])
    return Collection(base=base, learn=learn, pool=pool)
