"""The three representative dynamic-GNN models (paper §5).

Every model is expressed as a stack of (GCN, RNN) layer pairs with an explicit
*temporal carry* per layer:

    carry_in -(layer forward over a timeline slice)-> (outputs, carry_out)

The carry is exactly the paper's pi_b block-boundary data (§3.1): the RNN
state at the slice boundary plus the last (w-1) activations for windowed
temporal ops.  Single-device forward = one slice covering all T steps;
blocked gradient checkpointing (``repro.core.checkpoint``) scans over slices;
snapshot partitioning (``repro.core.partition``) inserts the two all-to-all
re-distributions around the temporal stage of the same layer functions.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import gcn as gcnlib
from repro.core import temporal
from repro.core.dtdg import DTDGBatch
from repro.obs import stages

Array = jax.Array


@dataclass(frozen=True)
class DynGNNConfig:
    model: str = "tmgcn"            # cdgcn | evolvegcn | tmgcn
    num_nodes: int = 1024
    num_steps: int = 16
    feat_in: int = 2                # paper: in/out degree features
    hidden: int = 6                 # paper: intermediate feature length 6
    out_dim: int = 6                # embedding length F'
    num_layers: int = 2
    window: int = 5                 # M-product / RNN window w
    num_classes: int = 2
    # execution knobs
    checkpoint_blocks: int = 1      # nb (1 = no checkpointing)
    use_pallas: bool = False        # the M-product's Pallas kernel
    precompute_first_agg: bool = False  # paper §5.5 first-layer SpMM reuse
    param_dtype: Any = jnp.float32

    def layer_dims(self) -> list[tuple[int, int, int]]:
        """[(d_in, d_gcn, d_out_of_layer)] per layer."""
        dims = []
        d = self.feat_in
        for l in range(self.num_layers):
            d_gcn = self.hidden
            if self.model == "cdgcn":
                d_layer_out = (self.out_dim if l == self.num_layers - 1
                               else self.hidden)
            else:
                d_layer_out = (self.out_dim if l == self.num_layers - 1
                               else self.hidden)
            dims.append((d, d_gcn, d_layer_out))
            d = d_layer_out
        return dims


# ------------------------------------------------------------- init ---------

def init_params(key: Array, cfg: DynGNNConfig) -> dict:
    params: dict = {"layers": []}
    for _l, (d_in, d_gcn, d_out) in enumerate(cfg.layer_dims()):
        key, k1, k2 = jax.random.split(key, 3)
        layer: dict = {}
        if cfg.model == "cdgcn":
            layer["gcn"] = gcnlib.init_gcn_params(k1, d_in, d_gcn,
                                                  cfg.param_dtype)
            # concat skip makes the LSTM input (d_in + d_gcn)-wide
            layer["lstm"] = temporal.init_lstm_params(
                k2, d_in + d_gcn, d_out, cfg.param_dtype)
        elif cfg.model == "evolvegcn":
            layer["evolve"] = temporal.init_weight_lstm_params(
                k1, d_in, d_out, cfg.param_dtype)
        elif cfg.model == "tmgcn":
            layer["gcn"] = gcnlib.init_gcn_params(k1, d_in, d_out,
                                                  cfg.param_dtype)
        else:
            raise ValueError(cfg.model)
        params["layers"].append(layer)
    key, kc = jax.random.split(key)
    scale = 1.0 / jnp.sqrt(cfg.out_dim)
    params["classifier"] = {
        "u": jax.random.uniform(kc, (cfg.out_dim, cfg.num_classes),
                                minval=-scale, maxval=scale,
                                dtype=jnp.float32).astype(cfg.param_dtype),
        "b": jnp.zeros((cfg.num_classes,), dtype=cfg.param_dtype),
    }
    return params


def init_layer_carry(cfg: DynGNNConfig, params: dict, layer: int,
                     num_local_nodes: int | None = None,
                     dtype=jnp.float32) -> Any:
    """Zero temporal carry (pi_0) for one layer.

    num_local_nodes: under snapshot partitioning the RNN stage is vertex-
    sharded, so carries are sized N/P locally.
    """
    n = num_local_nodes if num_local_nodes is not None else cfg.num_nodes
    d_in, d_gcn, d_out = cfg.layer_dims()[layer]
    if cfg.model == "cdgcn":
        return temporal.lstm_zero_state((n,), d_out, dtype)
    if cfg.model == "evolvegcn":
        p = params["layers"][layer]["evolve"]
        w0 = p["w0"]
        f_in, f_out = w0.shape
        return (w0, temporal.lstm_zero_state((f_out,), f_in, dtype))
    if cfg.model == "tmgcn":
        return jnp.zeros((cfg.window - 1, n, d_out), dtype=dtype)
    raise ValueError(cfg.model)


def init_carries(cfg: DynGNNConfig, params: dict,
                 num_local_nodes: int | None = None,
                 dtype=jnp.float32) -> list:
    return [init_layer_carry(cfg, params, l, num_local_nodes, dtype)
            for l in range(cfg.num_layers)]


# ---------------------------------------------------- layer-slice steps -----

@jax.named_scope(stages.SPATIAL)
def spatial_stage(cfg: DynGNNConfig, layer_params: dict, _layer: int,
                  x: Array, edges: Array, edge_weights: Array,
                  carry: Any, _t_offset: Array | int) -> tuple[Array, Any]:
    """The per-snapshot (communication-free) stage of one layer.

    x: (Ts, N, d_in) slice; edges: (Ts, E, 2); returns (Ts, N, d_mid).
    EvolveGCN folds the whole layer here (its LSTM runs over weights, which
    is also per-processor local — §5.5); returns the updated weight carry.
    """
    num_nodes = x.shape[1]
    if cfg.model == "evolvegcn":
        w_prev, state = carry
        ws, w_last, st_last = temporal.evolve_weights_from(
            layer_params["evolve"], w_prev, state, x.shape[0])

        def per_step(xt, et, wt, w_t):
            y0 = gcnlib.spatial_aggregate(xt, et, wt, num_nodes)
            return jax.nn.relu(y0 @ w_t)

        y = jax.vmap(per_step)(x, edges, edge_weights, ws)
        return y, (w_last, st_last)

    concat_skip = cfg.model == "cdgcn"

    def per_step(xt, et, wt):
        return gcnlib.gcn_apply(
            layer_params["gcn"], xt, et, wt, num_nodes,
            concat_skip=concat_skip,
            activation=(lambda v: v) if cfg.model == "tmgcn"
            else jax.nn.relu)

    y = jax.vmap(per_step)(x, edges, edge_weights)
    if cfg.model == "tmgcn":
        y = jax.nn.relu(y)
    return y, carry


@jax.named_scope(stages.TEMPORAL)
def temporal_stage(cfg: DynGNNConfig, layer_params: dict, _layer: int,
                   y: Array, carry: Any,
                   t_offset: Array | int) -> tuple[Array, Any]:
    """The per-vertex timeline stage of one layer. y: (Ts, Nloc, d_mid)."""
    if cfg.model == "cdgcn":
        z, new_state = temporal.lstm_scan(layer_params["lstm"], y,
                                          init_state=carry)
        return z, new_state
    if cfg.model == "evolvegcn":
        return y, carry  # already folded into the spatial stage
    if cfg.model == "tmgcn":
        z = temporal.m_product_with_prefix(y, carry, cfg.window, t_offset,
                                           use_pallas=cfg.use_pallas)
        new_prefix = jnp.concatenate([carry, y], axis=0)[-(cfg.window - 1):] \
            if cfg.window > 1 else carry
        return z, new_prefix
    raise ValueError(cfg.model)


def forward_slice(cfg: DynGNNConfig, params: dict, x: Array, edges: Array,
                  edge_weights: Array, carries: list,
                  t_offset: Array | int) -> tuple[Array, list]:
    """Full model over a contiguous timeline slice (single-device path)."""
    # Each layer owns one carry: the weight-LSTM state for EvolveGCN (used by
    # the spatial stage), the feature-RNN state / window prefix otherwise
    # (used by the temporal stage).
    evolve = cfg.model == "evolvegcn"
    new_carries = []
    h = x
    for l in range(cfg.num_layers):
        lp = params["layers"][l]
        h, c_sp = spatial_stage(cfg, lp, l, h, edges, edge_weights,
                                carries[l] if evolve else None, t_offset)
        h, c_tm = temporal_stage(cfg, lp, l, h,
                                 None if evolve else carries[l], t_offset)
        new_carries.append(c_sp if evolve else c_tm)
    return h, new_carries


# --------------------------------------------------------- full model -------

def forward(cfg: DynGNNConfig, params: dict, batch: DTDGBatch) -> Array:
    """Embeddings Z: (T, N, out_dim) — plain (non-blocked) forward."""
    carries = init_carries(cfg, params, dtype=batch.frames.dtype)
    z, _ = forward_slice(cfg, params, batch.frames, batch.edges,
                         batch.edge_weights, carries, 0)
    return z


def classify(params: dict, z: Array) -> Array:
    """Per-(t, u) logits via the shared projection U (§2.2)."""
    return z @ params["classifier"]["u"] + params["classifier"]["b"]


def node_loss(cfg: DynGNNConfig, params: dict, batch: DTDGBatch,
              labels: Array, label_mask: Array | None = None) -> Array:
    """Cross-entropy vertex classification over all (t, u)."""
    z = forward(cfg, params, batch)
    logits = classify(params, z)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if label_mask is not None:
        return jnp.sum(nll * label_mask) / jnp.maximum(label_mask.sum(), 1.0)
    return jnp.mean(nll)


def link_logits(params: dict, z_t: Array, pairs: Array) -> Array:
    """Link prediction head (§6.4): concat endpoint embeddings -> FC layer.

    z_t: (N, F'); pairs: (B, 2). The classifier U doubles as the FC layer by
    applying it to each endpoint and summing (equivalent to a (2F' x C) FC on
    the concatenation).
    """
    zu = jnp.take(z_t, pairs[:, 0], axis=0)
    zv = jnp.take(z_t, pairs[:, 1], axis=0)
    u = params["classifier"]["u"]
    b = params["classifier"]["b"]
    return zu @ u + zv @ u + b
