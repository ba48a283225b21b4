"""Asynchronous host->device delta streaming.

``PrefetchIterator`` runs the host encoder on a background thread and
issues ``jax.device_put`` there too, keeping up to ``depth`` staged items
ahead of the consumer: while the device executes ``apply_delta`` + the
train step for delta k, delta k+1 is being encoded and transferred.  The
numpy encode and the device execution overlap because both release the
GIL for their heavy parts.

``DeltaApplier`` owns the device-resident edge-buffer ring: ``apply_delta``
is jitted with donated input buffers, so the reconstructed snapshot is
written into the slot of the buffer being retired rather than a fresh
allocation — the stream runs in O(ring) device memory regardless of T.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator

import jax
import jax.numpy as jnp

from repro import obs, sanitize
from repro.core import graphdiff
from repro.core.graphdiff import FullSnapshot, SnapshotDelta
from repro.obs import stages
from repro.stream.wire import QuantizedDelta

_SENTINEL = object()


class PrefetchIterator:
    """Stage items of ``host_iter`` on a background thread.

    ``stage_fn`` (default ``jax.device_put``-based staging of stream items)
    runs on the worker; the bounded queue applies backpressure so at most
    ``depth`` staged items exist at once.  Exceptions on the worker are
    re-raised at the consumer's next ``__next__``; the iterator stays
    terminated (StopIteration) afterwards.  ``close()`` (also via the
    context-manager protocol) unblocks and retires the worker when the
    consumer abandons the stream early, releasing the staged buffers.
    """

    # _err is written by the worker and read by the consumer WITHOUT a
    # lock: the write happens-before the sentinel put, and the consumer
    # reads it only after get() returned that sentinel — the queue's
    # internal lock is the synchronization edge (dynlint: locks pass).
    _thread_owned = ("_err",)

    def __init__(self, host_iter: Iterable, stage_fn: Callable | None = None,
                 depth: int = 2):
        if depth < 1:
            raise ValueError("prefetch depth must be >= 1")
        self._stage = stage_fn if stage_fn is not None else stage_item
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self._done = False
        self._thread = threading.Thread(
            target=self._worker, args=(iter(host_iter),), daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Blocking put that still observes close(); False = shut down."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self, it: Iterator) -> None:
        trc = obs.get_tracer()
        try:
            while True:
                # the host iterator's work for one item (delta encode,
                # frame, labels), and the last pull that finds the end
                with trc.span("prefetch.encode", cat="prefetch"):
                    item = next(it, _SENTINEL)
                if item is _SENTINEL or self._stop.is_set():
                    return
                # staging span lives on the worker thread's trace track,
                # so overlap with the consumer's round spans is visible
                with trc.span("prefetch.stage", cat="prefetch"):
                    staged = self._stage(item)
                obs.inc("prefetch.items")
                if not self._put(staged):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            self._err = e
        finally:
            self._put(_SENTINEL)

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        with obs.span("prefetch.wait", cat="prefetch"):
            item = self._q.get()
        if item is _SENTINEL:
            self._done = True
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self) -> None:
        """Retire the worker and drop staged items (idempotent)."""
        self._stop.set()
        self._done = True
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=1.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def stage_item(item: Any, device=None) -> Any:
    """Ship one stream item's arrays to device (tuples recurse).

    ``device`` may be a concrete ``jax.Device`` (per-shard staging: the
    distributed streamed trainer pins each shard's delta to its own device)
    or a ``Sharding`` — anything ``jax.device_put`` accepts.  ``None`` keeps
    the single-device default placement.
    """
    put = (jax.device_put if device is None
           else (lambda x: jax.device_put(x, device)))
    if isinstance(item, tuple):
        return tuple(stage_item(x, device) for x in item)
    if isinstance(item, FullSnapshot):
        return FullSnapshot(edges=put(item.edges),
                            mask=put(item.mask),
                            values=put(item.values),
                            num_edges=item.num_edges)
    if isinstance(item, SnapshotDelta):
        return SnapshotDelta(drop_pos=put(item.drop_pos),
                             drop_mask=put(item.drop_mask),
                             add_edges=put(item.add_edges),
                             add_mask=put(item.add_mask),
                             values=put(item.values),
                             num_edges=item.num_edges)
    if isinstance(item, QuantizedDelta):
        # the narrow dtypes cross the host->device link as-is; widening
        # happens on device inside the decode jit (DeltaApplier)
        return QuantizedDelta(drop_pos=put(item.drop_pos),
                              drop_mask=put(item.drop_mask),
                              add_edges=put(item.add_edges),
                              add_mask=put(item.add_mask),
                              values_q=put(item.values_q),
                              values_scale=item.values_scale,
                              num_edges=item.num_edges)
    return put(item)


# One jitted apply_delta per donation mode, SHARED by every DeltaApplier:
# a fresh jax.jit wrapper per ring would re-trace/re-compile per instance,
# which the distributed trainer would pay P (double-buffered: 2P) times
# per epoch.  Device placement still follows the committed inputs.
_APPLY_DONATING = jax.jit(graphdiff.apply_delta, donate_argnums=(0, 1))
_APPLY_PLAIN = jax.jit(graphdiff.apply_delta)


@jax.named_scope(stages.DELTA_APPLY)
def _decode_apply(prev_edges, prev_mask, drop_pos, drop_mask, add_edges,
                  add_mask):
    """Widen a QuantizedDelta's narrow wire dtypes on device, then apply
    — one fused jit so the decode costs no extra device round."""
    return graphdiff.apply_delta(
        prev_edges, prev_mask, drop_pos.astype(jnp.int32),
        drop_mask.astype(jnp.float32), add_edges.astype(jnp.int32),
        add_mask.astype(jnp.float32))


_DECODE_DONATING = jax.jit(_decode_apply, donate_argnums=(0, 1))
_DECODE_PLAIN = jax.jit(_decode_apply)
# scale rides as an ARRAY argument: a python-float scale would bake a new
# constant (and a recompile) into the jit per delta
_DEQUANT = jax.jit(lambda q, scale: q.astype(jnp.float32) * scale)


class DeltaApplier:
    """Device-resident (edges, mask) buffer ring.

    ``consume`` turns a staged stream item into the current snapshot's
    device buffers: full snapshots swap in directly; deltas run the jitted
    ``apply_delta`` with the previous buffers DONATED, so XLA writes the
    new snapshot into the retiring slot (a 2-deep ring realized through
    input/output aliasing — no per-step allocation).
    """

    def __init__(self, max_edges: int, donate: bool = True, device=None):
        # ``device`` pins the ring to one shard's device (allocated there,
        # never staged through the default device): with committed inputs
        # the jitted apply (and every donation) stays on that device, so P
        # shard rings run truly independent per-device streams.
        self.edges = jnp.zeros((max_edges, 2), dtype=jnp.int32, device=device)
        self.mask = jnp.zeros((max_edges,), dtype=jnp.float32, device=device)
        self._apply = (sanitize.guard_donated(_APPLY_DONATING, (0, 1))
                       if donate else _APPLY_PLAIN)
        self._decode = (sanitize.guard_donated(_DECODE_DONATING, (0, 1))
                        if donate else _DECODE_PLAIN)

    def consume(self, item) -> tuple[jax.Array, jax.Array, jax.Array]:
        """-> (edges, mask, values) device arrays for this step.

        Accepts FullSnapshot, SnapshotDelta, and the narrow-wire
        QuantizedDelta (widened + dequantized on device).
        """
        if isinstance(item, FullSnapshot):
            self.edges = jnp.asarray(item.edges)
            self.mask = jnp.asarray(item.mask)
            values = jnp.asarray(item.values)
        elif isinstance(item, QuantizedDelta):
            self.edges, self.mask = self._decode(
                self.edges, self.mask, jnp.asarray(item.drop_pos),
                jnp.asarray(item.drop_mask), jnp.asarray(item.add_edges),
                jnp.asarray(item.add_mask))
            values = _DEQUANT(jnp.asarray(item.values_q),
                              jnp.asarray(item.values_scale))
        else:
            self.edges, self.mask = self._apply(
                self.edges, self.mask, jnp.asarray(item.drop_pos),
                jnp.asarray(item.drop_mask), jnp.asarray(item.add_edges),
                jnp.asarray(item.add_mask))
            values = jnp.asarray(item.values)
        # The documented ring contract (SlotStacker): these aliases are
        # donated by the NEXT consume — callers copy before then.  Under
        # REPRO_SANITIZE=1 a stale read raises instead of going silent.
        return self.edges, self.mask, values  # dynlint: allow[donation]


class SlotStacker:
    """Per-shard slot staging for blockwise streaming.

    The distributed trainer reconstructs ``slots`` consecutive snapshots on
    each shard before one sharded train step consumes them all.  The
    applier's ring DONATES its buffers on the next ``consume``, so each
    reconstructed snapshot must be copied out first: ``put(j, ...)``
    dispatches one O(E) copy per buffer (device program order guarantees
    the read happens before the next apply retires the ring slot), and
    ``arrays()`` stacks the slots into fresh (slots, E, ...) blocks once
    per round — O(slots * E) total, and nothing the assembled global
    array aliases is ever donated.
    """

    def __init__(self, slots: int):
        self._slots: list = [None] * slots

    _copy = staticmethod(jax.jit(jnp.copy))

    def put(self, j: int, edges, mask, values) -> None:
        self._slots[j] = (self._copy(edges), self._copy(mask),
                          self._copy(values))

    def arrays(self):
        """-> (edges (slots, E, 2), mask (slots, E), values (slots, E))."""
        es, ms, vs = zip(*self._slots, strict=True)
        return jnp.stack(es), jnp.stack(ms), jnp.stack(vs)
