"""Pallas TPU kernels: bitonic sort of int32 keys carrying payload lanes.

The aggregation (``repro.graph.segment.spmm``) sorts its lanes by the row
they write before the reduction kernel runs.  XLA's own TPU sort is fast,
but its code is large: a few MB per sort, which the chip keeps in HBM
beside the data.  This network is two small kernels, each compiled once
and called from loops, so a sort costs little code whatever its length.

The lanes are padded with ``INT32_MAX`` keys to ``P = 2**LOG_P`` and
viewed as ``(P / 128, 128)``; element ``i`` sits at row ``i // 128``, lane
``i % 128``.  Stage ``(k, j)`` of the network compares element ``i`` with
``i ^ 2**j`` and leaves the smaller key first where bit ``k`` of ``i`` is 0
(ascending runs of ``2**k``), the larger first where it is 1; ``k`` runs
from 1 to ``LOG_P``, ``j`` from ``k - 1`` down to 0.  Ties keep their
places: only the key order is defined.

* ``_block_kernel`` takes one block of ``BLOCK_ROWS`` rows into VMEM and
  runs every stage of one ``k`` whose stride lies inside the block: pairs
  of 32-row tiles for strides of 32 rows or more, row and lane rolls
  inside a tile below that.
* ``_cross_kernel`` runs one stage whose stride spans blocks: it takes a
  pair of blocks ``2**j`` elements apart and swaps them lane by lane.

Both copy their blocks with DMAs and write the arrays in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 1024         # rows (of 128 lanes) per block: 512 KB per array
_TILE = 32                # rows per tile: 4 vregs per op, so that the
                          # dependent ops of a stage overlap
_LANE_BITS = 7            # log2(128)
_TILE_BITS = 12           # log2(32 * 128): strides below this stay in a tile
_PAD_KEY = jnp.iinfo(jnp.int32).max


def _swaps(keep_min, own_key, other_key):
    """Where ``own`` takes ``other``'s lane: ``other`` holds the smaller
    key and ``own`` keeps the minimum, or the larger and it keeps the
    maximum.  (Mosaic selects no booleans, hence the logic.)"""
    return ((keep_min & (other_key < own_key))
            | (jnp.logical_not(keep_min) & (other_key > own_key)))


def _exchange(asc, own, other):
    """Compare-exchange of whole arrays: where ``asc``, ``own`` keeps the
    smaller key of the two; else the larger.  Returns the new ``own``
    and ``other`` lists (keys first)."""
    swap = _swaps(asc, own[0], other[0])
    return ([jnp.where(swap, b, a) for a, b in zip(own, other, strict=True)],
            [jnp.where(swap, a, b) for a, b in zip(own, other, strict=True)])


def _copies(src, dst, bufs, sem, block, rows):
    """DMAs of one block of every array: from ``src`` into the VMEM
    ``bufs``, and from them back to ``dst`` (aliased to ``src``)."""
    at = pl.ds(pl.multiple_of(block * rows, rows), rows)
    return ([pltpu.make_async_copy(h.at[at], v, sem.at[i])
             for i, (h, v) in enumerate(zip(src, bufs, strict=True))],
            [pltpu.make_async_copy(v, h.at[at], sem.at[i])
             for i, (h, v) in enumerate(zip(dst, bufs, strict=True))])


def _block_kernel(k_ref, *refs, n: int, rows: int):
    # refs: n inputs (aliased to the outputs), n outputs, n VMEM blocks,
    # one DMA semaphore array
    src, dst = refs[:n], refs[n:2 * n]
    bufs, sem = refs[2 * n:3 * n], refs[3 * n]
    b = pl.program_id(0)
    k = k_ref[0]
    ins, outs = _copies(src, dst, bufs, sem, b, rows)
    for c in ins:
        c.start()
    for c in ins:
        c.wait()

    tiles = rows // _TILE
    block_bits = (rows * 128).bit_length() - 1
    first_tile = b * tiles

    # strides of one tile or more: compare-exchange pairs of tiles
    def tile_stage(j, _):
        s = jnp.left_shift(1, j - _TILE_BITS)          # tile stride

        def pair(p, _):
            lo = (p // s) * (2 * s) + p % s
            hi = lo + s
            asc = jnp.right_shift(first_tile + lo, k - _TILE_BITS) % 2 == 0
            at_lo = pl.ds(pl.multiple_of(lo * _TILE, _TILE), _TILE)
            at_hi = pl.ds(pl.multiple_of(hi * _TILE, _TILE), _TILE)
            new_lo, new_hi = _exchange(asc, [v[at_lo, :] for v in bufs],
                                       [v[at_hi, :] for v in bufs])
            for v, a, c in zip(bufs, new_lo, new_hi, strict=True):
                v[at_lo, :] = a
                v[at_hi, :] = c
            return 0

        jax.lax.fori_loop(0, tiles // 2, pair, 0)
        return 0

    top = jnp.minimum(k, block_bits)
    jax.lax.fori_loop(0, jnp.maximum(top - _TILE_BITS, 0),
                      lambda i, c: tile_stage(top - 1 - i, c), 0)

    # strides inside a tile: row rolls, then lane rolls
    sub = jax.lax.broadcasted_iota(jnp.int32, (_TILE, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_TILE, 128), 1)

    def tile(t, _):
        at = pl.ds(pl.multiple_of(t * _TILE, _TILE), _TILE)
        vals = [v[at, :] for v in bufs]
        idx = ((first_tile + t) * _TILE + sub) * 128 + lane
        asc = jnp.right_shift(idx, k) % 2 == 0
        for j in range(_TILE_BITS - 1, -1, -1):
            axis, size = (1, 128) if j < _LANE_BITS else (0, _TILE)
            d = 1 << (j if j < _LANE_BITS else j - _LANE_BITS)
            lower = jnp.right_shift(idx, j) % 2 == 0
            other = [jnp.where(lower, pltpu.roll(x, size - d, axis),
                               pltpu.roll(x, d, axis)) for x in vals]
            keep_min = lower == asc
            swap = _swaps(keep_min, vals[0], other[0]) & (j < k)
            vals = [jnp.where(swap, o, x) for x, o in
                    zip(vals, other, strict=True)]
        for v, x in zip(bufs, vals, strict=True):
            v[at, :] = x
        return 0

    jax.lax.fori_loop(0, tiles, tile, 0)
    for c in outs:
        c.start()
    for c in outs:
        c.wait()


def _cross_kernel(kj_ref, *refs, n: int, rows: int):
    # refs: n inputs (aliased), n outputs, 2n VMEM blocks, DMA semaphores
    src, dst = refs[:n], refs[n:2 * n]
    lo_bufs, hi_bufs = refs[2 * n:3 * n], refs[3 * n:4 * n]
    sem = refs[4 * n]
    g = pl.program_id(0)
    block_bits = (rows * 128).bit_length() - 1
    k, j = kj_ref[0], kj_ref[1]
    s = jnp.left_shift(1, j - block_bits)              # block stride
    lo = (g // s) * (2 * s) + g % s
    hi = lo + s
    lo_in, lo_out = _copies(src, dst, lo_bufs, sem.at[0], lo, rows)
    hi_in, hi_out = _copies(src, dst, hi_bufs, sem.at[1], hi, rows)
    for c in lo_in + hi_in:
        c.start()
    for c in lo_in + hi_in:
        c.wait()
    asc = jnp.right_shift(lo, k - block_bits) % 2 == 0
    new_lo, new_hi = _exchange(asc, [v[...] for v in lo_bufs],
                               [v[...] for v in hi_bufs])
    for v, a in zip(lo_bufs, new_lo, strict=True):
        v[...] = a
    for v, a in zip(hi_bufs, new_hi, strict=True):
        v[...] = a
    for c in lo_out + hi_out:
        c.start()
    for c in lo_out + hi_out:
        c.wait()


def _call(kernel, scalars, arrays, grid, scratch, interpret):
    n = len(arrays)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return list(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[any_spec] * n, out_specs=[any_spec] * n,
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays],
        input_output_aliases={1 + i: i for i in range(n)},
        interpret=interpret,
    )(scalars, *arrays))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitonic_sort(key: jax.Array, *payloads: jax.Array,
                 block_rows: int = BLOCK_ROWS,
                 interpret: bool = False) -> tuple[jax.Array, ...]:
    """``(key, *payloads)`` of shape (L,), reordered by ascending ``key``
    (int32; ``INT32_MAX`` is reserved for padding).  Payloads are 32-bit.
    ``block_rows``, a power of two of at least 32, sets the VMEM block."""
    e = key.shape[0]
    log_p = max((e - 1).bit_length(), _TILE_BITS)
    p = 1 << log_p
    rows = min(block_rows, p // 128)
    block_bits = (rows * 128).bit_length() - 1
    nb = p // 128 // rows
    arrays = [jnp.pad(key, (0, p - e), constant_values=_PAD_KEY)]
    arrays += [jnp.pad(a, (0, p - e)) for a in payloads]
    arrays = [a.reshape(p // 128, 128) for a in arrays]
    n = len(arrays)
    vmem = [pltpu.VMEM((rows, 128), a.dtype) for a in arrays]
    block = functools.partial(
        _call, functools.partial(_block_kernel, n=n, rows=rows),
        grid=(nb,), scratch=vmem + [pltpu.SemaphoreType.DMA((n,))],
        interpret=interpret)
    cross = functools.partial(
        _call, functools.partial(_cross_kernel, n=n, rows=rows),
        grid=(max(nb // 2, 1),),
        scratch=vmem + vmem + [pltpu.SemaphoreType.DMA((2, n))],
        interpret=interpret)

    def level(k, arrs):
        arrs = jax.lax.fori_loop(
            0, jnp.maximum(k - block_bits, 0),
            lambda i, a: cross(jnp.stack([k, k - 1 - i]), a), arrs)
        return block(k[None], arrs)

    arrays = jax.lax.fori_loop(1, log_p + 1, level, arrays)
    return tuple(a.reshape(p)[:e] for a in arrays)
