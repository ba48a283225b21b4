"""AdamW with master-weight mixed precision, clipping, and LR schedules
(cosine; WSD — warmup-stable-decay — for MiniCPM).

Pure-pytree (no optax dependency): state mirrors the param tree, so the same
PartitionSpecs shard the optimizer state (m, v, fp32 master) as the params —
the layout the dry-run memory analysis accounts for.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs import stages

Array = jax.Array


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"          # cosine | wsd | constant
    stable_frac: float = 0.8          # WSD: fraction of steps at peak LR
    min_lr_frac: float = 0.1


def schedule_lr(cfg: AdamWConfig, step: Array) -> Array:
    s = step.astype(jnp.float32)
    warm = jnp.minimum(s / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "constant":
        frac = jnp.asarray(1.0)
    elif cfg.schedule == "cosine":
        t = jnp.clip((s - cfg.warmup_steps)
                     / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                     0.0, 1.0)
        frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) \
            * 0.5 * (1 + jnp.cos(jnp.pi * t))
    elif cfg.schedule == "wsd":
        # warmup -> stable plateau -> linear decay (MiniCPM, arXiv:2404.06395)
        stable_end = cfg.warmup_steps + cfg.stable_frac * \
            (cfg.total_steps - cfg.warmup_steps)
        decay_t = jnp.clip((s - stable_end)
                           / jnp.maximum(cfg.total_steps - stable_end, 1),
                           0.0, 1.0)
        frac = 1.0 - (1.0 - cfg.min_lr_frac) * decay_t
    else:
        raise ValueError(cfg.schedule)
    return cfg.lr * warm * frac


def init_state(params: Any) -> dict:
    zeros32 = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    master = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    return {"m": zeros32, "v": jax.tree.map(jnp.copy, zeros32),
            "master": master, "step": jnp.zeros((), jnp.int32)}


def global_norm(tree: Any) -> Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


@jax.named_scope(stages.OPTIMIZER)
def apply_updates(cfg: AdamWConfig, params: Any, grads: Any,
                  state: dict) -> tuple[Any, dict]:
    step = state["step"] + 1
    lr = schedule_lr(cfg, step)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
        if cfg.grad_clip > 0 else jnp.asarray(1.0)
    bc1 = 1 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, master):
        g = g.astype(jnp.float32) * scale
        m_new = cfg.b1 * m + (1 - cfg.b1) * g
        v_new = cfg.b2 * v + (1 - cfg.b2) * g * g
        mh = m_new / bc1
        vh = v_new / bc2
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * master
        master_new = master - lr * delta
        return master_new.astype(p.dtype), m_new, v_new, master_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    flat_ma = jax.tree.leaves(state["master"])
    new = [upd(*xs) for xs in zip(flat_p, flat_g, flat_m, flat_v, flat_ma,
                                  strict=True)]
    params_new = jax.tree.unflatten(treedef, [n[0] for n in new])
    state_new = {
        "m": jax.tree.unflatten(treedef, [n[1] for n in new]),
        "v": jax.tree.unflatten(treedef, [n[2] for n in new]),
        "master": jax.tree.unflatten(treedef, [n[3] for n in new]),
        "step": step,
    }
    return params_new, state_new
