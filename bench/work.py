"""Work that a training step requires, counted from shapes.

The counts are of the algorithm, whatever implements it:

* edges are the snapshot's real edges plus one self-loop per vertex,
  never padded lanes;
* feature rows are as wide as the model says (F=6 at float32), never
  padded to the chip's lanes;
* forward plus backward, with no recomputation and no optimizer; the
  backward leaves out what no parameter needs (the gradient of the
  input features);
* bytes are the least that must cross HBM: each array read once and
  written once per pass, gathered rows counted at their own width.

So no later kernel or layout change can push a share of the peak or of
the roofline that uses these counts above 100%.
"""

from __future__ import annotations

from dataclasses import dataclass

F32 = 4
INDEX = 4


@dataclass(frozen=True)
class Shape:
    """One TM-GCN snapshot step: ``num_nodes`` vertices, ``num_edges``
    real edges (self-loops not included), and the model's widths."""
    num_nodes: int
    num_edges: int
    feat_in: int
    hidden: int
    out_dim: int
    num_layers: int
    window: int
    num_classes: int

    @property
    def lanes(self) -> int:
        """Edges the aggregation visits: real edges plus self-loops."""
        return self.num_edges + self.num_nodes

    def widths(self) -> list[tuple[int, int]]:
        """(d_in, d_out) of each layer."""
        dims, d = [], self.feat_in
        for layer in range(self.num_layers):
            d_out = self.out_dim if layer == self.num_layers - 1 \
                else self.hidden
            dims.append((d, d_out))
            d = d_out
        return dims


def flops_per_snapshot(s: Shape) -> dict[str, int]:
    """Floating-point operations of one snapshot step.

    Forward: Laplacian weights (two degree counts and two products per
    edge, one rsqrt per vertex and side), per layer the aggregation
    (a multiply and an add per edge and feature), the dense transform,
    bias and ReLU, and the M-product (w terms per output); then the
    classifier and the cross-entropy.  Backward: the classifier's and
    each layer's weight gradient, ReLU and M-product transposes, and the
    aggregation's transpose for every layer but the first.
    """
    n, e = s.num_nodes, s.lanes
    c, w = s.num_classes, s.window
    fwd = 2 * e + 2 * n + 2 * e                 # degrees, rsqrt, weights
    bwd = 0
    for layer, (d_in, d_out) in enumerate(s.widths()):
        fwd += 2 * e * d_in                     # aggregation
        fwd += 2 * n * d_in * d_out + 2 * n * d_out   # transform, bias, relu
        fwd += w * n * d_out                    # M-product
        bwd += w * n * d_out + n * d_out        # M-product^T, relu'
        bwd += 2 * n * d_in * d_out + n * d_out  # dW, db
        if layer:
            bwd += 2 * n * d_in * d_out         # d(aggregate)
            bwd += 2 * e * d_in                 # aggregation^T
    fwd += 2 * n * s.out_dim * c + n * c + 4 * n * c + n   # logits, CE
    bwd += 4 * n * c + 2 * n * s.out_dim * c + n * c       # dlogits, dU, dc
    bwd += 2 * n * s.out_dim * c                # d(Z) of the last layer
    return {"forward": fwd, "backward": bwd, "total": fwd + bwd}


def bytes_per_snapshot(s: Shape) -> dict[str, int]:
    """Least HBM bytes of one snapshot step.

    Weights: the edge list (two indices and a value per lane) read for
    the degrees and again for the products, the weights written.  Each
    aggregation pass reads the edge list and its weights, gathers one
    source row per lane and writes the (N, d) result; the dense and
    temporal stages read and write (N, d) arrays, the M-product reading
    its w - 1 carried frames.  Backward passes mirror the forward ones.
    """
    n, e, w = s.num_nodes, s.lanes, s.window
    edge = 2 * INDEX + F32
    fwd = 2 * edge * e + F32 * e                # weights
    bwd = 0
    for layer, (d_in, d_out) in enumerate(s.widths()):
        agg = (2 * INDEX + F32) * e + F32 * d_in * e + F32 * n * d_in
        dense = F32 * n * (d_in + d_out)
        temporal = F32 * n * d_out * (w + 1)
        fwd += agg + dense + temporal
        bwd += dense + temporal
        if layer:
            bwd += agg
    logits = F32 * n * (s.out_dim + s.num_classes)
    fwd += logits
    bwd += logits
    return {"forward": fwd, "backward": bwd, "total": fwd + bwd}


def spmm_per_snapshot(s: Shape) -> dict[str, int]:
    """FLOPs and least HBM bytes of one snapshot's aggregation passes
    alone (``A_tilde @ X``: every layer's forward and every layer's
    transpose but the first's), by the rules above: a multiply and an add
    per lane and feature; per pass the edge list and its weights read,
    one source row gathered per lane at its own width, and the (N, d)
    result written."""
    n, e = s.num_nodes, s.lanes
    passes = [d_in for layer, (d_in, _) in enumerate(s.widths())
              for _ in range(2 if layer else 1)]
    flops = sum(2 * e * d for d in passes)
    nbytes = sum((2 * INDEX + F32) * e + F32 * d * e + F32 * n * d
                 for d in passes)
    return {"flops": flops, "bytes": nbytes}


def alltoall_bytes_per_round(win: int, num_nodes: int, feat: int,
                             layers: int, chips: int) -> float:
    """Bytes crossing the chips' links in one round of ``win`` snapshots
    under snapshot partitioning: two all-to-alls per layer over the
    (win, N, F) block, each moving the (P-1)/P of it that leaves its chip
    (from the program's ``dist.comm_volume.alltoall_round_payload``)."""
    if chips <= 1:
        return 0.0
    return 2.0 * layers * win * num_nodes * feat * (chips - 1) / chips * F32
