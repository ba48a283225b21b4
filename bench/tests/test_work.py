"""Work counts against hand counts at a tiny shape."""

import generate
import reference
import work
from cell import load_cell, shape_of

TINY = work.Shape(num_nodes=4, num_edges=3, feat_in=1, hidden=2, out_dim=2,
                  num_layers=2, window=2, num_classes=2)


def test_flops_by_hand():
    # lanes 7 = 3 edges + 4 self-loops; layers 1->2 and 2->2
    weights = 2 * 7 + 2 * 4 + 2 * 7
    layer0 = 2 * 7 * 1 + 2 * 4 * 1 * 2 + 2 * 4 * 2 + 2 * 4 * 2
    layer1 = 2 * 7 * 2 + 2 * 4 * 2 * 2 + 2 * 4 * 2 + 2 * 4 * 2
    head = 2 * 4 * 2 * 2 + 4 * 2 + 4 * 4 * 2 + 4
    back0 = (2 * 4 * 2 + 4 * 2) + (2 * 4 * 1 * 2 + 4 * 2)
    back1 = (2 * 4 * 2 + 4 * 2) + (2 * 4 * 2 * 2 + 4 * 2) \
        + 2 * 4 * 2 * 2 + 2 * 7 * 2
    back_head = 4 * 4 * 2 + 2 * 4 * 2 * 2 + 4 * 2 + 2 * 4 * 2 * 2
    got = work.flops_per_snapshot(TINY)
    assert got["forward"] == weights + layer0 + layer1 + head == 266
    assert got["backward"] == back0 + back1 + back_head == 276
    assert got["total"] == 542


def test_bytes_by_hand():
    edge = 12                               # two int32 indices, one f32
    weights = 2 * edge * 7 + 4 * 7
    agg0 = edge * 7 + 4 * 1 * 7 + 4 * 4 * 1
    agg1 = edge * 7 + 4 * 2 * 7 + 4 * 4 * 2
    dense0, dense1 = 4 * 4 * (1 + 2), 4 * 4 * (2 + 2)
    temporal = 4 * 4 * 2 * (2 + 1)
    logits = 4 * 4 * (2 + 2)
    got = work.bytes_per_snapshot(TINY)
    assert got["forward"] == (weights + agg0 + dense0 + temporal + agg1
                              + dense1 + temporal + logits) == 864
    assert got["backward"] == (dense0 + temporal + dense1 + temporal + agg1
                               + logits) == 540


def test_counts_real_edges_not_padded_lanes():
    """The shape a cell counts holds the smoothed snapshot's real edges,
    which the program pads up to a multiple of 128 lanes."""
    cell = load_cell("tmgcn-epinions-1chip")
    c = dict(cell.config, num_nodes=512, smoothed_edges_per_snapshot=1400)
    cell.config = c
    raw = generate.trace_for(c, cell.traffic, seed=5)
    real = max(e.shape[0] for e, _ in reference.smooth(
        raw, 512, c["window"], c["num_steps"]))
    s = shape_of(cell)
    assert s.num_edges == real
    assert s.lanes == real + 512
    assert work.flops_per_snapshot(s)["total"] \
        < work.flops_per_snapshot(
            work.Shape(**{**s.__dict__, "num_edges": real + 1}))["total"]


def test_full_size_shape():
    s = shape_of(load_cell("tmgcn-epinions-1chip"))
    assert (s.num_nodes, s.num_edges) == (755_200, 2_097_154)


def test_alltoall_bytes_match_program_model():
    from repro.dist.comm_volume import alltoall_round_payload
    for chips in (1, 2, 4):
        assert work.alltoall_bytes_per_round(8, 1024, 6, 2, chips) \
            == alltoall_round_payload(8, 1024, 6, 2, chips)


def test_spmm_per_snapshot_at_the_tiny_shape():
    """The aggregation's count as ``bench/stages.py`` gave it before it
    moved here: passes at widths 1, 2 and 2 over 7 lanes."""
    assert work.spmm_per_snapshot(TINY) == {"flops": 70, "bytes": 472}
    assert reference.model("tmgcn").spmm_per_snapshot is \
        work.spmm_per_snapshot
