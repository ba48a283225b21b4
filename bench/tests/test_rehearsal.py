"""The cell logic end to end on the CPU at a tiny size, host devices
standing in for the mesh's chips: the shape of the last line and a
correct comparison for every cell of ``tiny``."""

import json

import jax
import pytest

from tiny import CELLS, make_root, run_cell

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_last_line(root, workload):
    out = run_cell(root, workload, SEED)
    json.dumps(out)                                 # one JSON object
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 16
    assert set(out["metrics"]) == {"setup_s", "train_snapshots_per_s",
                                   "peak_hbm_gib"}
    assert all(set(m) == {"value", "unit"} for m in out["metrics"].values())
    assert out["metrics"]["train_snapshots_per_s"]["value"] > 0
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["platform"] == "cpu" and dev["count"] == len(jax.devices())
    for name, v in out["check"].items():
        assert 0 <= v["value"] <= v["limit"], name


def test_same_seed_same_readings(root):
    one_chip = next(n for n in sorted(CELLS) if CELLS[n]["chips"] == 1)
    a = run_cell(root, one_chip, SEED)
    b = run_cell(root, one_chip, SEED)
    assert a["check"] == b["check"]
    assert a["attempted"] % 16 == 0
