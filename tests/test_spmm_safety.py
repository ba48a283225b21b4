"""Regression tests for the segment_spmm interpret resolution and grid.

No hypothesis dependency (unlike test_kernels.py) so these always run: the
"Pallas" path must never silently interpret on a real accelerator backend,
and must interpret on CPU; the reduction's grid covers every lane once
whatever the degrees.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import segment
from repro.kernels.common import resolve_interpret
from repro.kernels.segment_spmm.ref import segment_spmm_ref
from repro.kernels.segment_spmm.segment_spmm import (CHUNK, ROWS, _schedule,
                                                     sorted_segment_sum)

N, E, F = 192, 800, 64


def _graph(seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N, size=(E,))
    dst = rng.integers(0, N, (E,))
    edges = np.stack([src, dst], axis=1).astype(np.int32)
    w = rng.normal(size=(E,)).astype(np.float32)
    x = rng.normal(size=(N, F)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(edges), jnp.asarray(w)


# ------------------------------------------------- interpret resolution ----

def test_interpret_resolves_from_backend(monkeypatch):
    """None -> interpret on CPU, compiled kernel everywhere else."""
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret(None) is True
    for backend in ("tpu", "gpu", "cuda"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert resolve_interpret(None) is False, backend


def test_segment_spmm_default_interpret_runs_on_cpu():
    """The kernel in the mode ``resolve_interpret(None)`` picks on the CPU
    backend runs there and matches the oracle, i.e. the resolution
    actually reaches pallas_call."""
    assert jax.default_backend() == "cpu"
    x, edges, w = _graph()
    keys, msgs = segment.sorted_lanes(x, edges[:, 0], edges[:, 1], w, N)
    got = sorted_segment_sum(keys, msgs, N,
                             interpret=resolve_interpret(None))
    want = segment_spmm_ref(x, edges, w, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hub", [False, True])
def test_schedule_visits_each_block_and_its_chunks_once(hub):
    """The reduction's grid: every block of ROWS output rows, in order,
    takes the chunks of CHUNK lanes that hold its lanes, each once (and
    one step if it has none), in at most blocks + chunks steps whatever
    the degrees; the steps past the live ones repeat the last one."""
    n = 20 * ROWS + 17
    rng = np.random.default_rng(7)
    # blocks 5-7 have no lanes, and block 8's start on a chunk boundary;
    # zero-weight lanes sort to the end with key n, out of range
    dst = np.concatenate([rng.integers(0, 5 * ROWS, 10 * CHUNK),
                          rng.integers(8 * ROWS, n, 26 * CHUNK),
                          np.full(4 * CHUNK, n)])
    if hub:
        dst[: 25 * CHUNK] = 3 * ROWS + 5
    keys = np.sort(dst).astype(np.int32)
    block, chunk, steps = (np.asarray(a) for a in _schedule(
        jnp.asarray(keys), n, ROWS, CHUNK))
    nb, nc, live = -(-n // ROWS), keys.shape[0] // CHUNK, int(steps[0])
    assert live <= nb + nc == block.shape[0]
    want = []
    for b in range(nb):
        lo, hi = np.searchsorted(keys, [b * ROWS, min((b + 1) * ROWS, n)])
        if lo == hi:
            want.append((b, min(lo // CHUNK, nc - 1)))
        else:
            want += [(b, c) for c in range(lo // CHUNK, -(-hi // CHUNK))]
    assert list(zip(block[:live].tolist(), chunk[:live].tolist())) == want
    assert set(block[live:].tolist()) <= {block[live - 1]}
    assert set(chunk[live:].tolist()) <= {chunk[live - 1]}
