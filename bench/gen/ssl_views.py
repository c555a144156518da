"""Two-view SSL traffic: latent factors rendered to "images", two augmented views.

The semantics are those of ``repro.data.synthetic.ssl_batch``, copied here so
that the benchmark owns its traffic:

- a fixed decoder ``W`` (latent_dim x input_dim, N(0, 1/latent_dim)) per seed;
- per batch, N(0, 1) latents rendered as ``tanh(latents @ W)``;
- per view and row, a channel jitter: scale ``1 + U(-j, j)``, shift ``U(-j, j)``;
- per view and coordinate, a mask that zeroes with probability ``mask_prob``
  (the "random crop" analogue);
- per view and coordinate, additive N(0, noise^2) pixel noise.

``host_batch`` renders one batch in NumPy on the host, as the program's own
``ssl_batch`` does; ``device_pool`` renders a pool of batches on the device in
one jitted call.  Both are pure functions of (seed, step), and both give a
batch in the form the ``ssl_mlp`` program takes: ``{"view1", "view2"}``, each
(batch, the configuration's ``input_dim``) float32.  The device pool
draws from the "rbg" generator (the chip's own bit generator): the TPU
compiler takes minutes over threefry draws of these sizes, seconds over rbg.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _params(traffic: dict) -> tuple[int, float, float, float]:
    return (int(traffic["latent_dim"]), float(traffic["jitter"]),
            float(traffic["mask_prob"]), float(traffic["noise"]))


def host_batch(traffic: dict, cfg: dict, seed: int, step: int) -> dict:
    """One batch of two (batch, input_dim) float32 views, rendered in NumPy."""
    latent, jitter, mask_prob, noise = _params(traffic)
    n, input_dim = int(traffic["batch"]), int(cfg["input_dim"])
    w = np.random.default_rng([seed, 0]).normal(size=(latent, input_dim)).astype(np.float32)
    w /= np.sqrt(latent)
    rng = np.random.default_rng([seed, 1, step])
    base = np.tanh(rng.normal(size=(n, latent)).astype(np.float32) @ w)
    views = []
    for _ in range(2):
        scale = 1.0 + jitter * rng.uniform(-1, 1, size=(n, 1)).astype(np.float32)
        shift = jitter * rng.uniform(-1, 1, size=(n, 1)).astype(np.float32)
        keep = rng.random(size=base.shape) > mask_prob
        v = (base * scale + shift) * keep.astype(np.float32)
        views.append(v + noise * rng.normal(size=base.shape).astype(np.float32))
    return {"view1": views[0], "view2": views[1]}


@partial(jax.jit, static_argnames=("n", "input_dim", "pool", "latent", "jitter", "mask_prob", "noise"))
def _pool(key, *, n, input_dim, pool, latent, jitter, mask_prob, noise):
    data = jax.random.key_data(key)
    k_w, k_rest = jax.random.split(jax.random.wrap_key_data(jnp.concatenate([data, data]), impl="rbg"))
    w = jax.random.normal(k_w, (latent, input_dim), jnp.float32) / np.sqrt(latent)

    def one(k):
        k_lat, k1, k2 = jax.random.split(k, 3)
        base = jnp.tanh(jax.random.normal(k_lat, (n, latent), jnp.float32) @ w)

        def view(kv):
            ks, kt, km, kn = jax.random.split(kv, 4)
            scale = 1.0 + jitter * jax.random.uniform(ks, (n, 1), minval=-1.0, maxval=1.0)
            shift = jitter * jax.random.uniform(kt, (n, 1), minval=-1.0, maxval=1.0)
            keep = jax.random.uniform(km, base.shape) > mask_prob
            return (base * scale + shift) * keep + noise * jax.random.normal(kn, base.shape)

        return view(k1), view(k2)

    v1, v2 = jax.vmap(one)(jax.random.split(k_rest, pool))
    return [{"view1": v1[i], "view2": v2[i]} for i in range(pool)]


def device_pool(traffic: dict, cfg: dict, key) -> list[dict]:
    """``traffic["pool"]`` batches made on the device; step s uses batch s mod pool."""
    latent, jitter, mask_prob, noise = _params(traffic)
    return _pool(key, n=int(traffic["batch"]), input_dim=int(cfg["input_dim"]), pool=int(traffic["pool"]),
                 latent=latent, jitter=jitter, mask_prob=mask_prob, noise=noise)
