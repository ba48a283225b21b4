"""The control — the reference computed in bfloat16, put in the
program's place — and the faults read in the reference fail the cell's
limits, while the program passes them; at a size the CPU holds (the
chip reads the same at the cells' own size with ``bench/control.py``)."""

import pytest

import cell as cell_mod
import check
import control
from tiny import CELLS, make_root

SEEDS = (1, 2, 3)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("small"), num_nodes=8192,
                     smoothed_edges=22_000)


@pytest.fixture(scope="module", params=sorted(CELLS))
def rows(request, root):
    cell = cell_mod.load_cell(request.param, root)
    return cell, [r for s in SEEDS
                  for r in control.readings(cell, s, program=True)]


def _over(cell, row):
    limits = cell.config["limits"]
    return [k for k in check.NUMBERS if row[k] > limits[k]]


def test_program_within_limits(rows):
    cell, rs = rows
    for r in rs:
        if r["kind"] == "program":
            assert not _over(cell, r), r


@pytest.mark.parametrize("kind", ["control", "half_batch", "no_exchange"])
def test_control_and_faults_fail(rows, kind):
    cell, rs = rows
    mine = [r for r in rs if r["kind"] == kind]
    if kind == "no_exchange":
        assert bool(mine) == (cell.chips > 1)
    for r in mine:
        assert _over(cell, r), r
