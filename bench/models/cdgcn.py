"""CD-GCN (paper 2109.07893, sections 5.1 and 6.1; the "concatenate"
dynamic GCN of Manessi, Rozza and Manzo, Dynamic Graph Convolutional
Networks, Pattern Recognition 2020, arXiv 1704.06199) for the plain
reference, in ``jax.numpy``; imports nothing of the program.

Per layer, on each snapshot t of the slice:

* Y0_t = L_t X_t, L the snapshot's Laplacian-weighted edges with
  self-loops (a scatter-add over the slice's edges);
* Y_t = relu(concat(Y0_t, Y0_t W + b)), d_in + hidden wide;
* (H_t, C_t) = LSTM(Y_t, H_{t-1}, C_{t-1}), gates in the order i, f, g,
  o; the state carried across slices and updates, zero at the epoch's
  start.

The last layer's H is the embedding; the classifier H U + c is shared
over (t, u).  Departures from Manessi et al.: the raw snapshots are used
with no smoothing (as the paper's CD-GCN runs); the activation on the
concatenation is ReLU; the gradient is not taken through the carried
state (one update per slice, the state entering a slice a constant).

The work counts follow ``bench/work.py``'s rules.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import work


def _widths(cfg) -> list[tuple[int, int, int]]:
    """(d_in, gcn width, LSTM width) of each layer."""
    dims, d = [], cfg["feat_in"]
    for layer in range(cfg["num_layers"]):
        h = cfg["out_dim"] if layer == cfg["num_layers"] - 1 \
            else cfg["hidden"]
        dims.append((d, cfg["hidden"], h))
        d = h
    return dims


def init_params(seed: int, cfg: dict) -> dict:
    """Uniform(+-1/sqrt(fan_in)) GCN weights, uniform(+-1/sqrt(H)) LSTM
    weights, uniform(+-1/sqrt(out_dim)) classifier, zero biases, drawn
    from ``PRNGKey(seed)``: split in three per layer (the GCN from the
    first part; the LSTM's split in two from the second, input weights
    first) and in two for the classifier."""
    key = jax.random.PRNGKey(seed)
    layers = []
    for d_in, d_gcn, h in _widths(cfg):
        key, k1, k2 = jax.random.split(key, 3)
        kx, kh = jax.random.split(k2)
        s, sh = 1.0 / jnp.sqrt(d_in), 1.0 / jnp.sqrt(h)
        layers.append({
            "gcn": {"w": jax.random.uniform(k1, (d_in, d_gcn), jnp.float32,
                                            -s, s),
                    "b": jnp.zeros((d_gcn,), jnp.float32)},
            "lstm": {"wx": jax.random.uniform(kx, (d_in + d_gcn, 4 * h),
                                              jnp.float32, -sh, sh),
                     "wh": jax.random.uniform(kh, (h, 4 * h), jnp.float32,
                                              -sh, sh),
                     "b": jnp.zeros((4 * h,), jnp.float32)}})
    key, kc = jax.random.split(key)
    s = 1.0 / jnp.sqrt(cfg["out_dim"])
    return {"layers": layers,
            "classifier": {"u": jax.random.uniform(
                kc, (cfg["out_dim"], cfg["num_classes"]), jnp.float32, -s, s),
                "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}}


def from_program(tree: dict) -> dict:
    """The program's CD-GCN parameter tree in this module's layout."""
    return {"layers": [{"gcn": lp["gcn"], "lstm": lp["lstm"]}
                       for lp in tree["layers"]],
            "classifier": tree["classifier"]}


def zero_carries(params: dict, num_nodes: int, cfg: dict) -> list:
    """Zero (h, c), each (N, H), per layer."""
    del cfg
    return [(jnp.zeros((num_nodes, lp["lstm"]["wh"].shape[0]), jnp.float32),
             jnp.zeros((num_nodes, lp["lstm"]["wh"].shape[0]), jnp.float32))
            for lp in params["layers"]]


def _lstm(p: dict, y, state, dtype):
    """The LSTM over the k steps of y (k, N, a) from state (h, c);
    returns (outputs (k, N, H), last state in float32)."""
    wx, wh, b = (p[k].astype(dtype) for k in ("wx", "wh", "b"))
    h, c = (s.astype(dtype) for s in state)
    hs = []
    for x in y:
        i, f, g, o = jnp.split(x @ wx + h @ wh + b, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        hs.append(h)
    return jnp.stack(hs), (h.astype(jnp.float32), c.astype(jnp.float32))


def slice_loss(params: dict, carries: list, frames, labels, edges, weights,
               t0, *, cfg: dict, dtype, groups: int = 1,
               fault: str | None = None):
    """Mean CE over the slice's (k, N) vertices, and the next carries."""
    del t0, groups
    if fault == "no_exchange":
        raise ValueError("CD-GCN has no configuration on several chips")
    n = frames.shape[1]
    h = frames.astype(dtype)
    new = []
    for lp, state in zip(params["layers"], carries, strict=True):
        w = lp["gcn"]["w"].astype(dtype)
        b = lp["gcn"]["b"].astype(dtype)

        def agg(x, e, wt):
            msg = x[e[:, 0]] * wt[:, None].astype(dtype)
            return jnp.zeros((n, x.shape[1]), dtype).at[e[:, 1]].add(msg)

        y0 = jax.vmap(agg)(h, edges, weights)
        y = jax.nn.relu(jnp.concatenate([y0, y0 @ w + b], axis=-1))
        h, state = _lstm(lp["lstm"], y, state, dtype)
        new.append(state)
    c = params["classifier"]
    logits = (h @ c["u"].astype(dtype) + c["b"].astype(dtype)).astype(
        jnp.float32)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               labels[..., None], axis=-1)[..., 0]
    if fault == "half_batch":
        nll = nll[:, :n // 2]
    return jnp.mean(nll), new


# ---------------------------------------------------------------- work ---

def _layers(s: work.Shape) -> list[tuple[int, int, int]]:
    """(d_in, gcn width, LSTM width) of each layer of shape ``s``."""
    return [(d_in, s.hidden, h) for d_in, h in s.widths()]


def lstm_per_snapshot(s: work.Shape) -> dict[str, int]:
    """FLOPs and least HBM bytes of both layers' LSTM, forward and
    backward, with a = d_in + hidden its input width and H its width.

    FLOPs: the gate products, 2 N (a + H) 4H per pass (the backward's
    weight gradients), plus the backward's input gradient 2 N 4H a (the
    GCN weights below need it); one op per element-wise op: forward the
    gate bias 4H, three sigmoids, two tanh and the three products and one
    sum of the state update (13 H per vertex), backward the bias gradient
    4H and the twenty of the cell's transpose (24 H per vertex).  The
    gradient of the carried state is not needed.  Bytes, per pass: the
    (N, a) input and the (N, H) h and c read, the new h and c written;
    the gates need not cross HBM.
    """
    n = s.num_nodes
    fwd = bwd = nbytes = 0
    for d_in, d_gcn, h in _layers(s):
        a = d_in + d_gcn
        fwd += 2 * n * (a + h) * 4 * h + 13 * n * h
        bwd += 2 * n * (a + h) * 4 * h + 2 * n * 4 * h * a + 24 * n * h
        nbytes += 2 * work.F32 * n * (a + 4 * h)
    return {"flops": fwd + bwd, "bytes": nbytes,
            "forward": fwd, "backward": bwd}


def flops_per_snapshot(s: work.Shape) -> dict[str, int]:
    """Floating-point operations of one snapshot step, by
    ``work.flops_per_snapshot``'s rules: the Laplacian weights; per layer
    the aggregation, the dense product and its bias, the ReLU over the
    concatenation (the concatenation itself is a copy) and the LSTM
    (``lstm_per_snapshot``); then the classifier and the cross-entropy.
    Backward: the classifier's and each layer's weight gradients, the
    ReLU's transpose, and for every layer but the first the gradient
    through the dense product and the skip, and the aggregation's
    transpose."""
    n, e, c = s.num_nodes, s.lanes, s.num_classes
    lstm = lstm_per_snapshot(s)
    fwd = 2 * e + 2 * n + 2 * e + lstm["forward"]
    bwd = lstm["backward"]
    for layer, (d_in, d_gcn, _) in enumerate(_layers(s)):
        a = d_in + d_gcn
        fwd += 2 * e * d_in                     # aggregation
        fwd += 2 * n * d_in * d_gcn + n * d_gcn + n * a  # transform, b, relu
        bwd += n * a                            # relu'
        bwd += 2 * n * d_in * d_gcn + n * d_gcn  # dW, db
        if layer:
            bwd += 2 * n * d_in * d_gcn + n * d_in  # d(Y0): product, skip
            bwd += 2 * e * d_in                 # aggregation^T
    fwd += 2 * n * s.out_dim * c + n * c + 4 * n * c + n   # logits, CE
    bwd += 4 * n * c + 2 * n * s.out_dim * c + n * c       # dlogits, dU, dc
    bwd += 2 * n * s.out_dim * c                # d(H) of the last layer
    return {"forward": fwd, "backward": bwd, "total": fwd + bwd}


def bytes_per_snapshot(s: work.Shape) -> dict[str, int]:
    """Least HBM bytes of one snapshot step, by
    ``work.bytes_per_snapshot``'s rules: the weights as there; per layer
    the aggregation pass, the dense stage reading (N, d_in) and writing
    the (N, a) concatenation, and the LSTM (``lstm_per_snapshot``);
    backward passes mirror the forward ones."""
    n, e = s.num_nodes, s.lanes
    edge = 2 * work.INDEX + work.F32
    fwd = 2 * edge * e + work.F32 * e           # weights
    bwd = 0
    for layer, (d_in, d_gcn, _) in enumerate(_layers(s)):
        agg = edge * e + work.F32 * d_in * e + work.F32 * n * d_in
        dense = work.F32 * n * (2 * d_in + d_gcn)
        fwd += agg + dense
        bwd += dense
        if layer:
            bwd += agg
    lstm = lstm_per_snapshot(s)["bytes"]
    logits = work.F32 * n * (s.out_dim + s.num_classes)
    fwd += logits + lstm // 2
    bwd += logits + lstm // 2
    return {"forward": fwd, "backward": bwd, "total": fwd + bwd}


spmm_per_snapshot = work.spmm_per_snapshot
