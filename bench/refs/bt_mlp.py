"""Plain reference of the SSL train step: Barlow Twins on an MLP encoder, LARS.

Written from the configuration file and the papers alone, in straightforward
``jax.numpy``; it imports nothing of the program.  One step is:

- encoder: backbone layers ``relu(x W + b)``, projector layers ``x W + b`` with
  a ReLU between them and none after the last, both views;
- standardization: per feature over the batch, ``(z - mean) / sqrt(var + eps)``
  with the biased variance;
- invariance: ``sum_i (1 - C_ii)^2`` with ``C_ii = sum_k z1[k, i] z2[k, i] / n``;
- regularizer on ``C = z1^T z2 / n``: R_off is the sum of squared off-diagonal
  entries (Zbontar et al., arXiv:2103.03230); the grouped R_sum (arXiv:2301.01569
  Eq. 13) first permutes the features of both views by
  ``jax.random.permutation(fold_in(perm_key, step), d)``, cuts C into b x b
  blocks, takes each block's summary vector ``s_i = sum_j B[j, (i + j) mod b]``,
  and sums ``|s_i|^q`` over all blocks and components except component 0 of the
  diagonal blocks;
- loss ``invariance + lam * R``; its gradient;
- LARS (You et al., arXiv:1708.03888) on matrices: ``g + wd p``, trust ratio
  ``tc |p| / (|g + wd p| + eps)``; on vectors plain momentum; ``p -= lr mu``;
- learning rate: linear warm-up from 0, then cosine to ``min_ratio * lr``.

Weights follow the configuration's initialization: layer i's ``W`` is
``normal(split(key, layers + 2)[i]) / sqrt(fan_in)``, biases zero.

``dtype`` and ``precision`` select the arithmetic: float32 at "highest" is the
reference; bfloat16 at "default" is the control that must fail the comparison.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def layer_dims(cfg: dict) -> tuple[list, list]:
    back = [int(cfg["input_dim"])] + [int(w) for w in cfg["backbone_widths"]]
    proj = [back[-1]] + [int(cfg["projector_width"])] * int(cfg["projector_layers"])
    return back, proj


def init(cfg: dict, key, dtype=jnp.float32) -> dict:
    back, proj = layer_dims(cfg)
    keys = jax.random.split(key, len(back) + len(proj))
    params, i = {"backbone": [], "projector": []}, 0
    for part, dims in (("backbone", back), ("projector", proj)):
        for a, b in zip(dims[:-1], dims[1:]):
            w = jax.random.normal(keys[i], (a, b), jnp.float32) / jnp.sqrt(a)
            params[part].append({"w": w.astype(dtype), "b": jnp.zeros((b,), dtype)})
            i += 1
    return params


def leaf_names(cfg: dict) -> list[str]:
    """Names of the parameter leaves, in ``jax.tree.leaves`` order."""
    back, proj = layer_dims(cfg)
    return [f"{part}[{i}].{k}" for part, dims in (("backbone", back), ("projector", proj))
            for i in range(len(dims) - 1) for k in ("b", "w")]


def _dot(a, b, precision):
    return jnp.dot(a, b, precision=precision)


def encode(params, x, precision):
    h = x
    for layer in params["backbone"]:
        h = jax.nn.relu(_dot(h, layer["w"], precision) + layer["b"])
    last = len(params["projector"]) - 1
    for i, layer in enumerate(params["projector"]):
        h = _dot(h, layer["w"], precision) + layer["b"]
        if i < last:
            h = jax.nn.relu(h)
    return h


def standardize(z, eps):
    mean = jnp.mean(z, axis=0, keepdims=True)
    zc = z - mean
    return zc / jnp.sqrt(jnp.mean(zc * zc, axis=0, keepdims=True) + eps)


def _sumvec_basis(b: int) -> np.ndarray:
    """(b*b, b) one-hot map from a block's entries (j, c) to component (c - j) mod b."""
    j, c = np.meshgrid(np.arange(b), np.arange(b), indexing="ij")
    s = np.zeros((b * b, b), np.float32)
    s[np.arange(b * b), ((c - j) % b).ravel()] = 1.0
    return s


def grouped_r_sum(c, b: int, q: int, precision):
    d = c.shape[0]
    nb = d // b
    blocks = c.reshape(nb, b, nb, b).transpose(0, 2, 1, 3).reshape(nb * nb, b * b)
    sv = _dot(blocks, jnp.asarray(_sumvec_basis(b), c.dtype), precision).reshape(nb, nb, b)
    vals = jnp.abs(sv) ** q
    return jnp.sum(vals) - jnp.sum(jnp.diagonal(vals[..., 0]))


def loss(cfg: dict, params, batch, perm_key, precision):
    dtype = params["backbone"][0]["w"].dtype
    z1 = standardize(encode(params, batch["view1"].astype(dtype), precision), cfg["eps"])
    z2 = standardize(encode(params, batch["view2"].astype(dtype), precision), cfg["eps"])
    n, d = z1.shape
    cii = jnp.sum(z1 * z2, axis=0) / n
    invariance = jnp.sum((1 - cii) ** 2)
    if cfg["reg"] == "sum":
        if cfg["permute"]:
            perm = jax.random.permutation(perm_key, d)
            z1, z2 = z1[:, perm], z2[:, perm]
        reg = grouped_r_sum(_dot(z1.T, z2, precision) / n, int(cfg["block_size"]), int(cfg["q"]), precision)
    else:
        c = _dot(z1.T, z2, precision) / n
        reg = jnp.sum(c * c) - jnp.sum(jnp.diagonal(c) ** 2)
    return invariance + cfg["lam"] * reg


def learning_rate(s: dict, step):
    step = jnp.asarray(step, jnp.float32)
    warm = s["lr"] * step / max(s["warmup_steps"], 1)
    prog = jnp.clip((step - s["warmup_steps"]) / max(s["total_steps"] - s["warmup_steps"], 1), 0.0, 1.0)
    cos = s["lr"] * (s["min_ratio"] + (1 - s["min_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < s["warmup_steps"], warm, cos)


def lars(o: dict, g, mu, p, lr):
    if p.ndim >= 2:
        g = g + o["weight_decay"] * p
        w_norm, g_norm = jnp.linalg.norm(p), jnp.linalg.norm(g)
        trust = jnp.where((w_norm > 0) & (g_norm > 0), o["trust_coefficient"] * w_norm / (g_norm + o["eps"]), 1.0)
        g = (trust * g).astype(p.dtype)
    mu = o["momentum"] * mu + g
    return p - lr.astype(p.dtype) * mu, mu


@partial(jax.jit, static_argnames=("cfg", "precision"))
def _step(cfg, precision, params, mu, batch, perm_key, step):
    cfg = dict(cfg)
    cfg["optimizer"], cfg["schedule"] = dict(cfg["optimizer"]), dict(cfg["schedule"])
    key = jax.random.fold_in(perm_key, step)
    value, grads = jax.value_and_grad(lambda p: loss(cfg, p, batch, key, precision))(params)
    lr = learning_rate(cfg["schedule"], step)
    out = jax.tree.map(lambda g, m, p: lars(cfg["optimizer"], g, m, p, lr), grads, mu, params)
    is_pair = lambda t: isinstance(t, tuple)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=is_pair)
    new_mu = jax.tree.map(lambda t: t[1], out, is_leaf=is_pair)
    norms = lambda tree: [jnp.linalg.norm(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)]
    return new_p, new_mu, value.astype(jnp.float32), norms(grads), norms(new_mu)


def _frozen(cfg: dict):
    keys = ("input_dim", "backbone_widths", "projector_width", "projector_layers", "reg", "q",
            "block_size", "permute", "lam", "eps")
    flat = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]) for k in keys)
    return flat + (("optimizer", tuple(sorted(cfg["optimizer"].items()))),
                   ("schedule", tuple(sorted(cfg["schedule"].items()))))


def readings(cfg: dict, batches: list, key, perm_key, *, dtype=jnp.float32, precision="highest") -> dict:
    """Train ``len(batches)`` steps from the seeded weights; return what is compared.

    ``loss``: each step's loss.  ``grad``: per-leaf norms of LARS's momentum
    after the first step, the first gradient as the optimizer keeps it.
    ``grad_raw``: per-leaf norms of the first raw gradient, which decide the
    leaves that are left out.  ``delta``: the parameters' change over all the
    steps, per leaf as a float32 array; ``change``: its per-leaf norms.
    """
    frozen = _frozen(cfg)
    params0 = init(cfg, key, dtype)
    params = params0
    mu = jax.tree.map(jnp.zeros_like, params)
    out = {"loss": []}
    for step, batch in enumerate(batches):
        params, mu, value, g_norms, mu_norms = _step(frozen, precision, params, mu, batch, perm_key,
                                                     jnp.asarray(step, jnp.int32))
        out["loss"].append(float(value))
        if step == 0:
            out["grad_raw"] = [float(x) for x in g_norms]
            out["grad"] = [float(x) for x in mu_norms]
    out["delta"] = [a.astype(jnp.float32) - b.astype(jnp.float32)
                    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params0))]
    out["change"] = [float(jnp.linalg.norm(x)) for x in out["delta"]]
    return out
