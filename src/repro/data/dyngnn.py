"""Dynamic-graph data pipeline.

Host-side stages (the CPU side of the paper's CPU->GPU boundary):
  1. snapshot generation / loading (ragged numpy edge lists),
  2. smoothing (edge-life / M-transform) — §5.4 preprocessing,
  3. graph-difference delta encoding per checkpoint block (§3.2),
  4. padding + Laplacian normalization -> device-ready DTDG blocks,
  5. label synthesis for vertex classification / link prediction tasks.

``DTDGPipeline.epoch_blocks()`` yields per-block device arrays exactly the
way the blocked trainer consumes them; ``transfer_bytes()`` reports the
graph-difference savings the benchmark records.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core import graphdiff, smoothing
from repro.core.dtdg import build_batch
from repro.graph import generate
from repro.stream import encoder as stream_encoder
from repro.stream import sharded as stream_sharded


# host memory the pipeline may spend to keep the encoded trace (the
# epinions shape at T=16 holds 310 MB; at its published T=512, 8 GB)
HOST_STREAM_CACHE_BYTES = 1 << 30


def _host_bytes(item) -> int:
    return sum(v.nbytes for v in vars(item).values()
               if isinstance(v, np.ndarray))


@dataclass
class DTDGDataset:
    snapshots: list[np.ndarray]
    values: list[np.ndarray] | None
    frames: np.ndarray              # (T, N, F)
    labels: np.ndarray              # (T, N)
    num_nodes: int

    @property
    def num_steps(self) -> int:
        return len(self.snapshots)


def dataset_from_snapshots(snaps: list[np.ndarray], num_nodes: int,
                           smoothing_mode: str = "none", window: int = 5,
                           edge_life: int = 5) -> DTDGDataset:
    """Raw snapshot edge lists -> device-ready DTDG dataset.

    The one post-processing path (smoothing §5.4 -> degree features ->
    synthetic labels) shared by the synthetic generator and the file
    loaders (``repro.run.data.EdgeListDTDG``).

    smoothing_mode: none (CD-GCN) | mproduct (TM-GCN) | edgelife (EvolveGCN).
    """
    values = None
    if smoothing_mode == "mproduct":
        snaps, values = smoothing.m_transform_sparse(snaps, window)
    elif smoothing_mode == "edgelife":
        snaps, values = smoothing.edge_life(snaps, edge_life)
    elif smoothing_mode != "none":
        raise ValueError(f"unknown smoothing_mode {smoothing_mode!r}")
    frames = np.stack([generate.degree_features(s, num_nodes)
                       for s in snaps])
    # synthetic-but-learnable labels: high in-degree (above median) = class 1
    med = np.median(frames[:, :, 0], axis=1, keepdims=True)
    labels = (frames[:, :, 0] > med).astype(np.int32)
    return DTDGDataset(snapshots=snaps, values=values, frames=frames,
                       labels=labels, num_nodes=num_nodes)


def synthetic_dataset(num_nodes: int, num_steps: int, density: float = 3.0,
                      churn: float = 0.1, smoothing_mode: str = "none",
                      window: int = 5, edge_life: int = 5,
                      seed: int = 0) -> DTDGDataset:
    """Evolving synthetic DTDG with degree features and synthetic labels."""
    snaps = generate.evolving_dynamic_graph(num_nodes, num_steps, density,
                                            churn, seed)
    return dataset_from_snapshots(snaps, num_nodes,
                                  smoothing_mode=smoothing_mode,
                                  window=window, edge_life=edge_life)


class DTDGPipeline:
    def __init__(self, ds: DTDGDataset, nb: int, max_edges: int | None = None,
                 use_graph_diff: bool = True):
        self.ds = ds
        self.nb = nb
        self.bsize = ds.num_steps // nb
        loops = ds.num_nodes
        if max_edges is None:
            max_edges = max(s.shape[0] for s in ds.snapshots) + loops
            max_edges = ((max_edges + 127) // 128) * 128
        self.max_edges = max_edges
        self.use_graph_diff = use_graph_diff
        self._batch = None
        # streamed transfer: vectorized encoder, churn-stat-sized pads.
        # Every epoch streams the same encoded trace, so its items are
        # kept while they fit HOST_STREAM_CACHE_BYTES of host memory and
        # an epoch only stages them; a larger trace is re-encoded lazily
        # each epoch (host_stream) rather than held whole.
        self.stream_stats = stream_encoder.measure_stats(
            ds.snapshots, ds.num_nodes, self.bsize, max_edges)
        report = stream_encoder.StreamReport()
        items: list | None = []
        held = self._stream_bytes = 0
        for item in self._encode(report):
            self._stream_bytes += item.payload_bytes
            held += _host_bytes(item)
            if items is not None and held <= HOST_STREAM_CACHE_BYTES:
                items.append(item)
            else:
                items = None
        # a resync is counted on the report of the epoch that meets it
        self.stream_items = items if report.resyncs == 0 else None

    @property
    def batch(self):
        """Device-ready padded batch (precomputed Laplacian weights,
        §5.5) — built LAZILY on first access: only the eager schedule
        (and evaluation) materializes the full (T, E, ...) tensors on
        device; the streamed and sampled schedules never touch it, so
        an out-of-core run can build the pipeline without allocating a
        device batch that would not fit."""
        if self._batch is None:
            self._batch = build_batch(self.ds.snapshots, self.ds.frames,
                                      self.ds.num_nodes,
                                      max_edges=self.max_edges,
                                      values=self.ds.values)
        return self._batch

    def transfer_bytes(self) -> dict:
        gd = self._stream_bytes
        base = graphdiff.naive_bytes(self.ds.snapshots)
        return {"graph_diff": gd, "naive": base,
                "ratio": gd / max(base, 1)}

    def _encode(self, report=None):
        return stream_encoder.iter_encode_stream(
            self.ds.snapshots, self.ds.values, self.ds.num_nodes,
            self.max_edges, self.bsize, self.stream_stats, report=report)

    def host_stream(self):
        """The encoded trace (what the prefetch thread drains): the kept
        items, else a lazy re-encode."""
        if self.stream_items is not None:
            return iter(self.stream_items)
        return self._encode()

    def sharded_streams(self, num_shards: int, wire: str = "none"):
        """Per-shard time-slice streams for snapshot partitioning
        (``wire="int8"`` = the narrow delta format, see stream.wire)."""
        return stream_sharded.encode_time_sliced(
            self.ds.snapshots, self.ds.values, self.ds.num_nodes,
            self.max_edges, self.bsize, num_shards, self.stream_stats,
            wire=wire)

    def blocked_arrays(self):
        """(frames, edges, edge_weights, labels) blocked (nb, bsize, ...)."""
        import jax.numpy as jnp

        def blk(a):
            t = a.shape[0]
            return a.reshape((self.nb, t // self.nb) + a.shape[1:])

        return (blk(self.batch.frames), blk(self.batch.edges),
                blk(self.batch.edge_weights),
                blk(jnp.asarray(self.ds.labels)))
