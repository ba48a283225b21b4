"""Hybrid partitioning (paper §6.5): snapshot groups x intra-snapshot
vertex sharding — for datasets whose individual snapshots are too large for
one device (AMLSim-Large: 2.2-3.2 B nnz, 44-64 GB per §6.5), or when
T < P would leave processors idle.

Mesh mapping: the 'data' axis carries snapshot groups (the paper's scheme),
the 'model' axis shards vertices WITHIN each snapshot:

  * features live vertex-sharded: local x is (T/Pd, N/Pm, F);
  * the GCN aggregate uses the blockwise pattern the paper cites ([23],
    Tripathy et al.): all-gather the frame over 'model', aggregate the
    local dst-edge shard, reduce-scatter back to vertex shards;
  * the temporal stage re-shards T-major -> N-major over 'data' exactly as
    in plain snapshot partitioning, except the vertex axis is already
    'model'-sharded, so each device ends with N/(Pd*Pm) timelines;
  * volume: O(T*N) over 'data' (unchanged — the paper's law) plus
    O(T/Pd * N) over 'model' for the intra-snapshot exchange.

Exactness vs the single-device reference is tested in
tests/test_hybrid.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import models as mdl
from repro.core import temporal

Array = jax.Array


def hybrid_forward(cfg: mdl.DynGNNConfig, mesh: Mesh,
                   data_axis: str = "data", model_axis: str = "model"):
    """Builds fn(params, frames, edges, ew) -> Z.

    Input layouts (global):
      frames (T, N, F)   sharded P(data, model, None)
      edges  (T, E, 2)   sharded P(data, model_edges, None) — edge shards
                         pre-partitioned by DST so each model shard owns
                         edges whose dst is local (dst ids LOCAL, src GLOBAL)
      ew     (T, E)      same sharding as edges' first two axes
    Output Z (T, N, F') sharded P(data, model, None).
    """
    pd = mesh.shape[data_axis]
    pm = mesh.shape[model_axis]

    def fn(params, frames, edges, ew):
        t_loc, n_loc, _ = frames.shape       # (T/Pd, N/Pm, F)
        h = frames
        for l in range(cfg.num_layers):
            lp = params["layers"][l]

            # ---- spatial stage: blockwise intra-snapshot SpMM ------------
            def per_snapshot(x_loc, e_loc, w_loc):
                x_full = jax.lax.all_gather(x_loc, model_axis, axis=0,
                                            tiled=True)      # (N, F)
                msgs = jnp.take(x_full, e_loc[:, 0], axis=0) \
                    * w_loc[:, None].astype(x_full.dtype)
                return jax.ops.segment_sum(msgs, e_loc[:, 1],
                                           num_segments=n_loc)

            y0 = jax.vmap(per_snapshot)(h, edges, ew)   # (T/Pd, N/Pm, F)
            if cfg.model == "cdgcn":
                y1 = y0 @ lp["gcn"]["w"] + lp["gcn"]["b"]
                y = jax.nn.relu(jnp.concatenate([y0, y1], axis=-1))
            else:
                y = jax.nn.relu(y0 @ lp["gcn"]["w"] + lp["gcn"]["b"])

            # ---- temporal stage: T-major -> N-major over 'data' ----------
            y = jax.lax.all_to_all(y, data_axis, split_axis=1,
                                   concat_axis=0, tiled=True)
            # (T, N/(Pd*Pm), F')
            carry = mdl.init_layer_carry(cfg, params, l,
                                         num_local_nodes=y.shape[1],
                                         dtype=y.dtype)
            z, _ = mdl.temporal_stage(cfg, lp, l, y, carry, 0)
            h = jax.lax.all_to_all(z, data_axis, split_axis=0,
                                   concat_axis=1, tiled=True)
        return h

    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(data_axis, model_axis, None),
                  P(data_axis, model_axis, None),
                  P(data_axis, model_axis)),
        out_specs=P(data_axis, model_axis, None),
        check_vma=False)


def partition_edges_for_hybrid(edges_padded, weights, masks,
                               num_nodes: int, pm: int,
                               max_local_edges: int):
    """Host-side: per snapshot, split edges into Pm dst-shards (dst LOCAL,
    src GLOBAL), stacked along the edge axis so spec P(data, model) shards
    correctly.  Returns (T, Pm*E_loc, 2) edges and matching weights."""
    import numpy as np
    t_steps = edges_padded.shape[0]
    n_per = num_nodes // pm
    out_e = np.zeros((t_steps, pm, max_local_edges, 2), dtype=np.int32)
    out_w = np.zeros((t_steps, pm, max_local_edges), dtype=np.float32)
    for t in range(t_steps):
        e = np.asarray(edges_padded[t])
        m = np.asarray(masks[t]) > 0
        ev = e[m]
        wv = np.asarray(weights[t])[m]
        owner = ev[:, 1] // n_per
        for p in range(pm):
            sel = ev[owner == p]
            ws = wv[owner == p]
            k = min(sel.shape[0], max_local_edges)
            out_e[t, p, :k, 0] = sel[:k, 0]
            out_e[t, p, :k, 1] = sel[:k, 1] % n_per
            out_w[t, p, :k] = ws[:k]
    return (out_e.reshape(t_steps, pm * max_local_edges, 2),
            out_w.reshape(t_steps, pm * max_local_edges))
