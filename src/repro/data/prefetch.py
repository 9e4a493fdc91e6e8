"""Async double-buffered host->device batch staging (`repro.data.prefetch`).

The mesh trainer (DESIGN.md §9) dispatches a chunk of K train steps per jit
call; this module keeps that dispatch fed. Two pieces:

  * `stack_blocks` turns a per-step batch stream into pre-stacked `(K, ...)`
    numpy blocks following a chunk schedule. It is a plain generator, so the
    *generation* cost (the synthetic corpus samplers are Python loops) runs
    wherever the generator is consumed — inline in the fit loop, or on the
    prefetch worker thread, where it overlaps the in-flight chunk.
  * `ChunkPrefetcher` is the double buffer: a daemon worker thread pulls
    host-side blocks from the source, commits them to device with
    `jax.device_put` against the data-shard sharding (`batch_put`), and parks
    them in a bounded queue (depth 2: block i+1 stages while chunk i
    computes). Neither batch generation nor the H2D copy ever sits on the
    dispatch critical path.

The prefetcher is backend-agnostic: the "blocks" are arbitrary pytrees.

The worker's profiler spans: `prefetch.make` (drawing the next item from the
source: batch generation and stacking; the last one finds the end of a finite
stream) and `prefetch.put` (the device placement).
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Optional, Sequence

import numpy as np


def stack_blocks(batches: Iterator[dict], sizes: Sequence[int]) -> Iterator[dict]:
    """Stack consecutive per-step batches into `(K, ...)` numpy blocks.

    `sizes[i]` batches are consumed from `batches` for block i — the chunk
    schedule of the fit loop (`trainloop.chunk_schedule`). The per-step stream
    is consumed in order and unmodified: unstacking the blocks reproduces it
    exactly (tests/test_trainloop.py locks this in).
    """
    for k in sizes:
        rows = []
        for _ in range(k):
            try:
                rows.append(next(batches))
            except StopIteration:
                raise ValueError(
                    f"data stream exhausted mid-chunk (got {len(rows)} of {k} "
                    f"batches); a fit needs n_steps batches — pass a "
                    f"long-enough stream or lower spec.steps") from None
        yield {key: np.stack([np.asarray(r[key]) for r in rows])
               for key in rows[0]}


def batch_put(ctx) -> Callable:
    """Leaf-wise device placement for stacked `(K, B, ...)` batch blocks on
    `ctx`.

    On a distributed ShardCtx the batch dimension, axis 1, is committed
    against the data axes, so the H2D transfer lands each worker's shard
    directly on its devices; everything else replicates. On the local
    (meshless) ctx this is a plain transfer, byte-identical to `jnp.asarray`.
    """
    import jax
    import jax.numpy as jnp

    if not getattr(ctx, "distributed", False):
        return lambda tree: jax.tree.map(jnp.asarray, tree)

    from jax.sharding import NamedSharding, PartitionSpec

    axes = tuple(a for a in ctx.data_axes if a in ctx.mesh.shape)
    n_shards = int(np.prod([ctx.mesh.shape[a] for a in axes])) if axes else 1

    def one(x):
        spec = [None] * np.ndim(x)
        if axes and np.ndim(x) > 1 and x.shape[1] % n_shards == 0:
            spec[1] = axes if len(axes) > 1 else axes[0]
        return jax.device_put(x, NamedSharding(ctx.mesh, PartitionSpec(*spec)))

    return lambda tree: jax.tree.map(one, tree)


class ChunkPrefetcher:
    """Double-buffered async host->device staging of a batch/block stream.

    A daemon worker thread iterates `source`, applies `put` (device placement;
    defaults to `jax.device_put`) and parks the committed arrays in a bounded
    queue. Iterating the prefetcher yields device-resident items in order;
    an exception raised by the source or the transfer re-raises at the
    consuming end. `close()` is idempotent and safe mid-stream (the SIGTERM
    drain path): it unblocks and joins the worker without consuming the rest
    of the source.
    """

    _DONE = object()

    def __init__(self, source: Iterable, put: Optional[Callable] = None,
                 depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1 (got {depth})")
        if put is None:
            import jax

            put = jax.device_put
        self._put = put
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._lock = threading.Lock()   # guards _err (worker writes, consumer reads)
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._work, args=(iter(source),),
            name="chunk-prefetch", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- worker
    def _work(self, it: Iterator) -> None:
        try:
            from jax.profiler import TraceAnnotation

            while not self._stop.is_set():
                try:
                    with TraceAnnotation("prefetch.make"):
                        item = next(it)
                except StopIteration:
                    break
                with TraceAnnotation("prefetch.put"):
                    item = self._put(item)
                self._offer(item)
        except BaseException as e:  # surfaced from __next__, not swallowed
            with self._lock:
                self._err = e
        self._offer(self._DONE)

    def _offer(self, item) -> None:
        """put() that close() can always unblock."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return
            except queue.Full:
                continue

    # ----------------------------------------------------------- consumer
    def __iter__(self) -> "ChunkPrefetcher":
        return self

    def __next__(self):
        while True:
            try:
                item = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    # worker gone without the sentinel landing (e.g. the
                    # queue was drained by close()): treat as end-of-stream
                    item = self._DONE
                    break
        if item is self._DONE:
            err = self._take_err()
            if err is not None:
                raise err
            raise StopIteration
        return item

    def _take_err(self) -> Optional[BaseException]:
        with self._lock:
            err, self._err = self._err, None
            return err

    def close(self) -> None:
        """Stop the worker and join it; pending staged items are dropped."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10.0)

    def __enter__(self) -> "ChunkPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
