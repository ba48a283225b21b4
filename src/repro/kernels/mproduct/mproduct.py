"""Pallas TPU kernel: banded TTM (tensor-times-matrix) for the TM-GCN
M-product (paper §5.3).

Y = M x_1 X with M the (T x T) banded lower-triangular averaging matrix
M[t, k] = 1/min(w, t) on max(1, t-w+1) <= k <= t (1-indexed).  Materializing
M is O(T^2); the band never needs more than w rows of X per output row.

TPU adaptation: grid (T / T_BLK, NF / NF_BLK).  Each step emits a
(T_BLK x NF_BLK) output tile from TWO consecutive input tiles (the current
tile plus its predecessor — the band reaches back at most w-1 <= T_BLK rows),
building the (T_BLK x 2*T_BLK) band weights on the fly from iota comparisons
and contracting on the MXU.  VMEM: 3 tiles — never the T x T matrix.

``t_offset`` (the global index of row 0, needed by blocked checkpointing /
snapshot partitioning, where the op runs on a timeline slice) is a traced
scalar operand: it rides in as a (1, 1) int32 tile so the same compiled
kernel serves every block of the scan.

Constraints: w - 1 <= T_BLK; rows whose band reaches before row 0 while
t_offset > 0 are garbage and must be discarded by the caller (the
``m_product_with_prefix`` pattern prepends the (w-1)-frame prefix and slices
it back off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(toff_ref, x_prev_ref, x_cur_ref, out_ref, *, window: int,
            t_block: int):
    i = pl.program_id(0)
    t_offset = toff_ref[0, 0]
    x = jnp.concatenate([x_prev_ref[...], x_cur_ref[...]], axis=0)
    # Global 1-indexed timestep of each output row / input column.
    row = jax.lax.broadcasted_iota(jnp.int32, (t_block, 2 * t_block), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t_block, 2 * t_block), 1)
    g = row + i * t_block + t_offset + 1            # output step
    k = col + (i - 1) * t_block + t_offset + 1      # input step
    in_band = (k <= g) & (k > g - window) & (k >= 1)
    denom = jnp.maximum(jnp.minimum(window, g), 1).astype(x.dtype)
    band = jnp.where(in_band, 1.0, 0.0).astype(x.dtype) / denom
    # HIGHEST: the band weights (1/min(w, t)) and x must not be rounded to
    # bf16 on the MXU — the op is the f32 M-product of the dense oracle
    out_ref[...] = jax.lax.dot(band, x,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32
                               ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "t_block", "nf_block",
                                             "interpret"))
def banded_ttm(x: jax.Array, window: int, t_offset: jax.Array | int = 0,
               t_block: int | None = None, nf_block: int = 128,
               interpret: bool = False) -> jax.Array:
    """x: (T, NF) -> (T, NF); Y[t] = mean of x[max(0,t-w+1)..t] (global idx)."""
    t, nf = x.shape
    if t_block is None:
        # large enough for the band; T is padded up to a multiple of it
        t_block = max(8, ((window - 1 + 7) // 8) * 8)
    if window - 1 > t_block:
        raise ValueError(f"window-1={window-1} must be <= t_block={t_block}")
    pad_t = (-t) % t_block
    pad_nf = (-nf) % nf_block
    if pad_t or pad_nf:
        x = jnp.pad(x, ((0, pad_t), (0, pad_nf)))
    t_p, nf_p = x.shape
    toff = jnp.asarray(t_offset, dtype=jnp.int32).reshape(1, 1)
    grid = (t_p // t_block, nf_p // nf_block)
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, t_block=t_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda _i, _j: (0, 0)),
            # predecessor tile (clamped at 0; out-of-band weights are zero)
            pl.BlockSpec((t_block, nf_block),
                         lambda i, j: (jnp.maximum(i - 1, 0), j)),
            pl.BlockSpec((t_block, nf_block), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((t_block, nf_block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t_p, nf_p), x.dtype),
        interpret=interpret,
    )(toff, x, x)
    return out[:t, :nf]
