"""Plain reference of the benchmark's training cells, in float32
``jax.numpy`` at the matmul precision the configuration states
(``matmul_precision``: the program's TPU default, one bfloat16 pass, for
both cells; ``highest`` gives the exact float32 product).

It imports nothing of the program and takes nothing the program made: it
smooths the benchmark's own raw snapshots, derives the degree features
and labels, draws the initial weights from the seed, and trains the
configuration's model (``bench/models/<model>.py``) with AdamW, one
update per slice of ``k`` snapshots (paper 2109.07893, sections 5 and
6.1):

* smoothing ``mproduct``: hat(A)_t = sum_k M[t, k] A_k, M the banded
  averaging matrix with M[t, k] = 1 / min(w, t) for t - w < k <= t
  (1-indexed); ``none`` keeps the raw snapshot with unit values;
* features: (in-degree, out-degree) of the smoothed snapshot; label 1
  where the in-degree lies above that snapshot's median;
* Laplacian: the snapshot's edges plus self-loops, each weighted
  val / sqrt((1 + deg_out(src)) (1 + deg_in(dst))), degrees counting
  edges, self-loops included;
* loss: the model's mean cross-entropy over every vertex of the slice;
* AdamW with global-norm clipping and a warmed-up cosine schedule.

``dtype=jnp.bfloat16`` computes the forward and backward in bfloat16
(the update stays float32): that is the control, which the check must
refuse.  ``fault`` plants one of the faults the check must refuse.
"""

from __future__ import annotations

import functools
import importlib.util
import math
from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

MODELS = Path(__file__).resolve().parent / "models"


@functools.cache
def model(name: str):
    """The reference model ``bench/models/<name>.py`` (loaded once, so
    that its functions key the jit cache)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_model_{name}", MODELS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ host data

def smooth(raw: list[np.ndarray], num_nodes: int, window: int,
           steps: int, mode: str = "mproduct"
           ) -> list[tuple[np.ndarray, np.ndarray]]:
    """(edges (E, 2) int32, values (E,) float32) of smoothed snapshots
    0 .. steps - 1, edges in (src, dst) order."""
    if mode == "none":
        return [(raw[t], np.ones(raw[t].shape[0], np.float32))
                for t in range(steps)]
    if mode != "mproduct":
        raise ValueError(f"the reference has no smoothing {mode!r}")
    keys = [e[:, 0].astype(np.int64) * num_nodes + e[:, 1]
            for e in raw[:steps]]
    uniq, inv = np.unique(np.concatenate(keys), return_inverse=True)
    ids = np.split(inv, np.cumsum([k.size for k in keys])[:-1])
    out = []
    for t in range(steps):
        count = np.bincount(np.concatenate(ids[max(0, t - window + 1):t + 1]),
                            minlength=uniq.size)
        held = np.flatnonzero(count)
        edges = np.stack([uniq[held] // num_nodes, uniq[held] % num_nodes],
                         axis=1)
        out.append((edges.astype(np.int32),
                    (count[held] / min(window, t + 1)).astype(np.float32)))
    return out


def features(edges: np.ndarray, num_nodes: int) -> tuple[np.ndarray,
                                                          np.ndarray]:
    """((N, 2) float32 in/out-degree features, (N,) int32 labels)."""
    din = np.bincount(edges[:, 1], minlength=num_nodes)
    dout = np.bincount(edges[:, 0], minlength=num_nodes)
    labels = (din > np.median(din)).astype(np.int32)
    return np.stack([din, dout], axis=1).astype(np.float32), labels


@dataclass
class Slice:
    """k consecutive snapshots on the device, padded to one edge count."""
    t0: int
    frames: jax.Array       # (k, N, 2)
    labels: jax.Array       # (k, N)
    edges: jax.Array        # (k, E, 2), self-loops appended
    weights: jax.Array      # (k, E) Laplacian weights, 0 on pad lanes


def _laplacian(edges: np.ndarray, values: np.ndarray, num_nodes: int,
               pad_to: int) -> tuple[np.ndarray, np.ndarray]:
    loops = np.arange(num_nodes, dtype=np.int32)
    src = np.concatenate([edges[:, 0], loops])
    dst = np.concatenate([edges[:, 1], loops])
    val = np.concatenate([values, np.ones(num_nodes, np.float32)])
    din = np.bincount(dst, minlength=num_nodes).astype(np.float32)
    dout = np.bincount(src, minlength=num_nodes).astype(np.float32)
    w = (val / np.sqrt((1.0 + dout[src]) * (1.0 + din[dst]))).astype(
        np.float32)
    pad = pad_to - src.shape[0]
    e = np.stack([np.pad(src, (0, pad)), np.pad(dst, (0, pad))], axis=1)
    return e.astype(np.int32), np.pad(w, (0, pad))


def build_slices(raw: list[np.ndarray], num_nodes: int, window: int,
                 slice_len: int, num_slices: int,
                 smoothing: str = "mproduct") -> list[Slice]:
    """The first ``num_slices`` slices of ``slice_len`` snapshots."""
    steps = slice_len * num_slices
    snaps = smooth(raw, num_nodes, window, steps, smoothing)
    pad_to = max(e.shape[0] for e, _ in snaps) + num_nodes
    out = []
    for s in range(num_slices):
        part = snaps[s * slice_len:(s + 1) * slice_len]
        fl = [features(e, num_nodes) for e, _ in part]
        lw = [_laplacian(e, v, num_nodes, pad_to) for e, v in part]
        out.append(Slice(
            t0=s * slice_len,
            frames=jnp.asarray(np.stack([f for f, _ in fl])),
            labels=jnp.asarray(np.stack([lab for _, lab in fl])),
            edges=jnp.asarray(np.stack([e for e, _ in lw])),
            weights=jnp.asarray(np.stack([w for _, w in lw]))))
    return out


@dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float
    warmup_steps: int
    total_steps: int
    min_lr_frac: float

    def rate(self, step: int) -> float:
        """Warm-up, then cosine decay to ``min_lr_frac`` of ``lr``."""
        warm = min(step / max(self.warmup_steps, 1), 1.0)
        t = min(max((step - self.warmup_steps)
                    / max(self.total_steps - self.warmup_steps, 1), 0.0),
                1.0)
        frac = self.min_lr_frac + (1 - self.min_lr_frac) * 0.5 * (
            1 + math.cos(math.pi * t))
        return self.lr * warm * frac


@partial(jax.jit,
         static_argnames=("loss_fn", "cfg", "dtype", "groups", "fault"))
def _loss_and_grad(params, carries, frames, labels, edges, weights, t0, *,
                   loss_fn, cfg, dtype, groups, fault):
    (loss, new), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, carries, frames, labels, edges, weights, t0, cfg=cfg,
        dtype=dtype, groups=groups, fault=fault)
    return loss, new, grads


class _Frozen(dict):
    """A configuration that can key a jit cache (a static argument)."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


@dataclass
class Readings:
    """What the check compares: per-step losses, the first update's
    clipped gradient, and each parameter's change over the first
    updates."""
    losses: list[float]
    first_grad: dict
    change: dict


def train(mod, cfg: dict, params: dict, slices: list[Slice], opt: AdamW, *,
          change_after: int, dtype=jnp.float32, precision: str | None = None,
          groups: int = 1, fault: str | None = None) -> Readings:
    """Train model ``mod`` of configuration ``cfg`` over ``slices``, one
    AdamW update each, from ``params``, at the configuration's matmul
    precision unless ``precision`` says otherwise; the change is taken
    after the first ``change_after`` updates."""
    with jax.default_matmul_precision(precision
                                      or cfg["matmul_precision"]):
        n = slices[0].frames.shape[1]
        carries = mod.zero_carries(params, n, cfg)
        master = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        m = jax.tree.map(jnp.zeros_like, master)
        v = jax.tree.map(jnp.zeros_like, master)
        losses, first, change = [], None, None
        for step, s in enumerate(slices, start=1):
            loss, carries, g = _loss_and_grad(
                master, carries, s.frames, s.labels, s.edges, s.weights,
                jnp.int32(s.t0), loss_fn=mod.slice_loss, cfg=_Frozen(cfg),
                dtype=dtype, groups=groups, fault=fault)
            losses.append(float(loss))
            g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
            norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, opt.grad_clip / jnp.maximum(norm, 1e-9))
            g = jax.tree.map(lambda x: x * scale, g)
            if first is None:
                first = g
            m = jax.tree.map(lambda a, x: opt.b1 * a + (1 - opt.b1) * x, m, g)
            v = jax.tree.map(lambda a, x: opt.b2 * a + (1 - opt.b2) * x * x,
                             v, g)
            bc1, bc2 = 1 - opt.b1 ** step, 1 - opt.b2 ** step
            lr = opt.rate(step)
            master = jax.tree.map(
                lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2)
                                                       + opt.eps)
                                          + opt.weight_decay * p),
                master, m, v)
            if step == change_after:
                change = jax.tree.map(
                    lambda a, b: a - b.astype(jnp.float32), master, params)
    return Readings(losses=losses, first_grad=jax.device_get(first),
                    change=jax.device_get(change))
