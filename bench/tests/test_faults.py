"""A run with the timed path broken underneath must come out not
correct: each fault is planted in the program, the rest of the run is
the cell's own (without the look for a chip), at a tiny size."""

import jax
import jax.numpy as jnp
import pytest

from tiny import CELLS, make_root, run_cell

SEED = 2**31 + 23


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


def _unchanged_state(mp):
    """The step returns the parameters and optimizer state it was given."""
    from repro.optim import adamw
    mp.setattr(adamw, "apply_updates",
               lambda cfg, params, grads, state: (params, state))


def _half_batch(mp):
    """Half of the vertices left out of the loss, the mean over the rest."""
    from repro.stream import train_loop
    orig = train_loop.slice_nll

    def nll(params, z, labels):
        v = orig(params, z, labels)
        half = v.shape[-1] // 2
        return jnp.concatenate([2.0 * v[..., :half],
                                jnp.zeros_like(v[..., half:])], axis=-1)

    mp.setattr(train_loop, "slice_nll", nll)


def _no_exchange(mp):
    """The all-to-alls leave out the exchange between chips: each chip
    re-lays out its own block as if it had received the others'."""
    from repro.core import partition

    def local(y, axis, num_procs, split_axis):
        t, n, f = y.shape
        if split_axis == 1:
            y = y.reshape(t, num_procs, n // num_procs, f)
            return jnp.swapaxes(y, 0, 1).reshape(t * num_procs,
                                                 n // num_procs, f)
        y = y.reshape(num_procs, t // num_procs, n, f)
        return jnp.swapaxes(y, 0, 1).reshape(t // num_procs, n * num_procs, f)

    mp.setattr(partition, "_major_all_to_all", local)


def _altered_loss(mp):
    """The loss is altered where it is produced (its gradient is not)."""
    from repro.stream import train_loop
    orig = train_loop.slice_nll
    mp.setattr(train_loop, "slice_nll",
               lambda params, z, labels: orig(params, z, labels) + 0.01)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_loss": _altered_loss, "no_exchange": _no_exchange}
CASES = [(w, f) for w in sorted(CELLS)
         for f in FAULTS if f != "no_exchange" or CELLS[w]["chips"] > 1]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(root, monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    jax.clear_caches()
    out = run_cell(root, workload, SEED)
    assert out["correct"] is False
    over = [k for k, v in out["check"].items() if v["value"] > v["limit"]]
    assert over, out["check"]


def test_sound_program_is_correct(root):
    mesh = next(n for n in sorted(CELLS) if CELLS[n]["chips"] > 1)
    jax.clear_caches()
    assert run_cell(root, mesh, SEED)["correct"] is True
