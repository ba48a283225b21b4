"""Graph-difference based host->device snapshot transfer (paper §3.2).

Real dynamic graphs evolve slowly, so consecutive snapshots share most of
their topology.  Instead of shipping every snapshot as a full (indices,
values) sparse body, we ship, per step:

  * the positions (within the previous snapshot's edge list) of edges that
    DISAPPEAR  (A_i^ext  -> a drop list),
  * the new edges that APPEAR (A_{i+1}^ext),
  * all values of the new snapshot (values rarely overlap, per the paper).

TPU adaptation: the scarce link is host RAM -> HBM (the infeed), playing the
role of the paper's PCIe CPU->GPU link.  The *encoder* runs on host numpy in
the data pipeline; the *decoder* (reconstruction of the padded edge list from
the previous device-resident buffer plus the delta) runs on device in jitted
JAX so the reconstructed snapshot never round-trips through the host.

Bytes accounting is exact and is what `benchmarks/graphdiff_bench.py` reports
against the naive full-transfer baseline (paper Fig. 4).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import stages

Array = jax.Array


def _edge_key(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    return edges[:, 0].astype(np.int64) * num_nodes \
        + edges[:, 1].astype(np.int64)


@dataclass
class SnapshotDelta:
    """Host-side delta between consecutive snapshots (padded, static shapes)."""
    drop_pos: np.ndarray    # (D_max,) int32 positions into prev edge list
    drop_mask: np.ndarray   # (D_max,) f32
    add_edges: np.ndarray   # (A_max, 2) int32
    add_mask: np.ndarray    # (A_max,) f32
    values: np.ndarray      # (E_max,) f32 — values of the new snapshot
    num_edges: int          # valid edge count of the new snapshot

    @property
    def payload_bytes(self) -> int:
        """Bytes actually shipped (valid lanes only, like the paper counts)."""
        d = int(self.drop_mask.sum())
        a = int(self.add_mask.sum())
        return d * 4 + a * 8 + self.num_edges * 4


@dataclass
class FullSnapshot:
    edges: np.ndarray   # (E_max, 2)
    mask: np.ndarray    # (E_max,)
    values: np.ndarray  # (E_max,)
    num_edges: int

    @property
    def payload_bytes(self) -> int:
        return self.num_edges * 8 + self.num_edges * 4


def encode_stream(snapshots: list[np.ndarray],
                  values: list[np.ndarray] | None,
                  num_nodes: int, max_edges: int,
                  block_size: int) -> list[FullSnapshot | SnapshotDelta]:
    """Encode a snapshot sequence for blocked transfer.

    The first snapshot of each checkpoint block is shipped in full (the GPU
    holds nothing to diff against at a block boundary — §6.2's
    (bsize-1)/bsize benefit ratio); subsequent snapshots ship as deltas.
    Padded static shapes: drops/adds padded to max_edges (callers may size
    tighter from dataset statistics).
    """
    out: list[FullSnapshot | SnapshotDelta] = []
    # The encoder mirrors the DEVICE-side edge ordering: after a delta is
    # applied on device, the buffer holds survivors (previous device order,
    # compacted) followed by the added edges.  Drop positions must index THIS
    # ordering, not the original snapshot file order.
    device_edges: np.ndarray | None = None
    for i, snap in enumerate(snapshots):
        vals = (values[i] if values is not None
                else np.ones((snap.shape[0],), dtype=np.float32))
        if i % block_size == 0:
            e = np.zeros((max_edges, 2), dtype=np.int32)
            m = np.zeros((max_edges,), dtype=np.float32)
            v = np.zeros((max_edges,), dtype=np.float32)
            e[:snap.shape[0]] = snap
            m[:snap.shape[0]] = 1.0
            v[:snap.shape[0]] = vals
            out.append(FullSnapshot(edges=e, mask=m, values=v,
                                    num_edges=snap.shape[0]))
            device_edges = snap.copy()
        else:
            prev = device_edges
            pk = _edge_key(prev, num_nodes)
            ck = _edge_key(snap, num_nodes)
            drop_sel = ~np.isin(pk, ck)
            add_sel = ~np.isin(ck, pk)
            drop_pos = np.nonzero(drop_sel)[0].astype(np.int32)
            adds = snap[add_sel]
            dp = np.zeros((max_edges,), dtype=np.int32)
            dm = np.zeros((max_edges,), dtype=np.float32)
            dp[:drop_pos.shape[0]] = drop_pos
            dm[:drop_pos.shape[0]] = 1.0
            ae = np.zeros((max_edges, 2), dtype=np.int32)
            am = np.zeros((max_edges,), dtype=np.float32)
            ae[:adds.shape[0]] = adds
            am[:adds.shape[0]] = 1.0
            # New device order: survivors (device order) then adds.
            device_edges = np.concatenate([prev[~drop_sel], adds], axis=0)
            v = np.zeros((max_edges,), dtype=np.float32)
            cur_lookup = {int(k): float(val) for k, val in zip(ck, vals, strict=True)}
            new_keys = _edge_key(device_edges, num_nodes)
            v[:new_keys.shape[0]] = np.asarray(
                [cur_lookup[int(k)] for k in new_keys], dtype=np.float32)
            out.append(SnapshotDelta(drop_pos=dp, drop_mask=dm, add_edges=ae,
                                     add_mask=am, values=v,
                                     num_edges=snap.shape[0]))
    return out


@jax.named_scope(stages.DELTA_APPLY)
def apply_delta(prev_edges: Array, prev_mask: Array, drop_pos: Array,
                drop_mask: Array, add_edges: Array, add_mask: Array
                ) -> tuple[Array, Array]:
    """Device-side reconstruction of the next snapshot's padded edge list.

    1. Invalidate dropped positions in the previous buffer.
    2. Compact surviving edges to the front (stable argsort on validity).
    3. Append the added edges after the survivors.

    All shapes static (E_max); runs inside jit.
    """
    e_max = prev_edges.shape[0]
    keep = prev_mask
    keep = keep * (1.0 - jnp.zeros_like(prev_mask)
                   .at[drop_pos].add(drop_mask, mode="drop"))
    keep = jnp.clip(keep, 0.0, 1.0)
    # Stable compaction: order by (not kept), preserving original order.
    order = jnp.argsort(1.0 - keep, stable=True)
    survivors = jnp.take(prev_edges, order, axis=0)
    surv_mask = jnp.take(keep, order)
    n_surv = jnp.sum(surv_mask).astype(jnp.int32)
    # Place added edges right after the survivors.
    add_count = jnp.cumsum(add_mask.astype(jnp.int32)) - 1
    tgt = jnp.where(add_mask > 0, n_surv + add_count, e_max)  # e_max = drop
    new_edges = survivors * surv_mask[:, None].astype(prev_edges.dtype)
    new_edges = new_edges.at[tgt].set(add_edges, mode="drop")
    new_mask = surv_mask.at[tgt].set(add_mask, mode="drop")
    return new_edges, new_mask


def decode_stream(stream: list[FullSnapshot | SnapshotDelta],
                  max_edges: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Replay a stream on device; returns [(edges, mask)] per step (testing)."""
    apply_jit = jax.jit(apply_delta)
    out = []
    prev_e = jnp.zeros((max_edges, 2), dtype=jnp.int32)
    prev_m = jnp.zeros((max_edges,), dtype=jnp.float32)
    for item in stream:
        if isinstance(item, FullSnapshot):
            prev_e = jnp.asarray(item.edges)
            prev_m = jnp.asarray(item.mask)
        else:
            prev_e, prev_m = apply_jit(prev_e, prev_m,
                                       jnp.asarray(item.drop_pos),
                                       jnp.asarray(item.drop_mask),
                                       jnp.asarray(item.add_edges),
                                       jnp.asarray(item.add_mask))
        out.append((np.asarray(prev_e), np.asarray(prev_m)))
    return out


def stream_bytes(stream: list[FullSnapshot | SnapshotDelta]) -> int:
    return sum(s.payload_bytes for s in stream)


def naive_bytes(snapshots: list[np.ndarray]) -> int:
    """Baseline: full (indices, values) per snapshot (paper's `Base`)."""
    return sum(s.shape[0] * 12 for s in snapshots)
