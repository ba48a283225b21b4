"""TM-GCN (paper 2109.07893, section 5.3) for the plain reference, in
``jax.numpy``; imports nothing of the program.

Per layer: Y = relu(L X W + b), L the snapshot's Laplacian-weighted edges
with self-loops; then the M-product along time,
Z_t = (1 / min(w, t)) * (sum of the last min(w, t) Y's), t 1-indexed and
global, carried across slices as the last w - 1 Y's.  The classifier
Z U + c is shared over (t, u).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import work


def init_params(seed: int, cfg: dict) -> dict:
    """Uniform(+-1/sqrt(fan_in)) GCN weights and classifier, zero biases,
    drawn from ``PRNGKey(seed)`` split in three per layer (the first part
    for the weights) and in two for the classifier."""
    key = jax.random.PRNGKey(seed)
    layers = []
    d = cfg["feat_in"]
    for layer in range(cfg["num_layers"]):
        key, k1, _ = jax.random.split(key, 3)
        d_out = cfg["out_dim"] if layer == cfg["num_layers"] - 1 \
            else cfg["hidden"]
        s = 1.0 / jnp.sqrt(d)
        layers.append({"w": jax.random.uniform(k1, (d, d_out), jnp.float32,
                                               -s, s),
                       "b": jnp.zeros((d_out,), jnp.float32)})
        d = d_out
    key, kc = jax.random.split(key)
    s = 1.0 / jnp.sqrt(cfg["out_dim"])
    return {"layers": layers,
            "classifier": {"u": jax.random.uniform(
                kc, (cfg["out_dim"], cfg["num_classes"]), jnp.float32, -s, s),
                "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}}


def from_program(tree: dict) -> dict:
    """The program's TM-GCN parameter tree in this module's layout."""
    return {"layers": [lp["gcn"] for lp in tree["layers"]],
            "classifier": tree["classifier"]}


def zero_carries(params: dict, num_nodes: int, cfg: dict) -> list:
    return [jnp.zeros((cfg["window"] - 1, num_nodes, lp["w"].shape[1]),
                      jnp.float32) for lp in params["layers"]]


def _m_product(y, carry, window: int, t0, groups: int, fault: str | None):
    """Z for the k steps of y (k, N, F) given the last w-1 Y's before
    them; returns (Z, next carry)."""
    k = y.shape[0]
    full = jnp.concatenate([carry.astype(y.dtype), y], axis=0)
    zs = []
    for j in range(k):
        g = t0 + j + 1                       # 1-indexed global step
        count = jnp.minimum(window, g)
        first = window - count              # oldest frame of the window
        if fault == "no_exchange":
            # each chip's steps see only the frames it holds itself
            first = jnp.maximum(first, window - 1 - j % (k // groups))
        # a masked sum, not a dot: no product of the temporal stage may
        # round its inputs as the stated matmul precision does
        keep = (jnp.arange(window) >= first)[:, None, None]
        zs.append(jnp.sum(jnp.where(keep, full[j:j + window], 0), axis=0)
                  / count.astype(y.dtype))
    return jnp.stack(zs), full[-(window - 1):].astype(jnp.float32)


def slice_loss(params: dict, carries: list, frames, labels, edges, weights,
               t0, *, cfg: dict, dtype, groups: int = 1,
               fault: str | None = None):
    """Mean CE over the slice's (k, N) vertices, and the next carries."""
    n = frames.shape[1]
    h = frames.astype(dtype)
    new = []
    for lp, carry in zip(params["layers"], carries, strict=True):
        w = lp["w"].astype(dtype)
        b = lp["b"].astype(dtype)

        def agg(x, e, wt):
            msg = x[e[:, 0]] * wt[:, None].astype(dtype)
            return jnp.zeros((n, x.shape[1]), dtype).at[e[:, 1]].add(msg)

        y = jax.nn.relu(jax.vmap(agg)(h, edges, weights) @ w + b)
        h, c = _m_product(y, carry, cfg["window"], t0, groups, fault)
        new.append(c)
    c = params["classifier"]
    logits = (h @ c["u"].astype(dtype) + c["b"].astype(dtype)).astype(
        jnp.float32)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               labels[..., None], axis=-1)[..., 0]
    if fault == "half_batch":
        nll = nll[:, :n // 2]
    return jnp.mean(nll), new


flops_per_snapshot = work.flops_per_snapshot
bytes_per_snapshot = work.bytes_per_snapshot
spmm_per_snapshot = work.spmm_per_snapshot
