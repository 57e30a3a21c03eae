"""Sharding-aware async device prefetch: overlap H2D with compute.

The io layer already overlaps *host* work (DataLoader workers collate on
background threads/processes), but until this module nothing moved
batches onto the mesh ahead of the step: `Trainer.step` paid a blocking
`jax.device_put` per batch tensor on the dispatch thread — host work
serialized against device compute, exactly the stall tf.data-style
pipelines (Murray et al.) and GSPMD-era trainers exist to hide. A
`DevicePrefetcher` closes that gap: a background thread pulls batches
from any iterator/DataLoader, places every leaf with the consumer's
sharding (the trainer hands its cached per-(key, ndim) `NamedSharding`
via `sharding_for`), and keeps an N-deep queue of already-on-device
batches. The consumer's `next()` returns arrays whose sharding already
matches, so the trainer's hot path skips `device_put` entirely — H2D
runs concurrently with the previous step's compute.

Multi-process safety: when the target sharding spans non-addressable
devices (a real multi-host mesh), each host feeds only its own shard —
placement goes through `jax.make_array_from_process_local_data`, so the
per-host DataLoader (DistributedBatchSampler) contract is preserved.

Lifecycle contract:
  - iterator exhaustion propagates as StopIteration to the consumer;
  - a worker exception is re-raised in the consumer thread (the
    original exception object, so handlers written for the source's
    failure mode keep working);
  - `close()` (or the context-manager exit) cancels the worker, drains
    the queue and joins the thread — safe mid-epoch, idempotent;
  - the queue is bounded (`depth`): a stalled consumer backpressures
    the worker instead of buffering the epoch onto the device.

Always on: the plain attribute `wait_s` (seconds the consumer's
`next()` blocked on the queue, beside `batches_prefetched`) and the
spans `input.wait` (consumer) / `input.h2d` (worker), which a profiler
capture holds (observability/trace.py SPANS).

Failure injection + observability (both zero-cost when disabled):
  - chaos site `io.prefetch.delay` — a slow host input pipeline;
  - `io.prefetch.queue_depth` gauge, `io.h2d.seconds` histogram
    (placement dispatch + ready, measured on the worker thread) and
    `io.prefetch.batches` counter, all catalogued in
    observability/metrics.py.
"""
from __future__ import annotations

import queue as _queue
import threading
import time
import weakref

import numpy as np
import jax

from paddle_tpu import observability
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import chaos

__all__ = ["DevicePrefetcher", "prefetch_to_device"]

# queue item tags (the payload rides alongside)
_ITEM, _DONE, _ERR = 0, 1, 2


class DevicePrefetcher:
    """Iterate `source`, yielding batches whose array leaves are already
    placed on device (per `sharding_for`), prefetched `depth` ahead by a
    background thread.

    sharding_for: callable ``(key, ndim) -> Sharding | None`` — the
        target sharding for a leaf (`key` is the nearest enclosing dict
        key, None outside dicts). None places on the default device.
        `Trainer.data_iter` passes the trainer's cached batch shardings
        here so prefetcher and step agree by object identity.
    depth: queue bound — up to `depth` placed batches wait in the
        queue, plus ONE more held by the worker while it blocks on the
        full queue (budget device headroom for depth + 1).
    """

    def __init__(self, source, *, sharding_for=None, depth=2):
        self._it = iter(source)
        self._sharding_for = sharding_for
        self.depth = max(1, int(depth))
        self._q: _queue.Queue = _queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._finished = False
        self.batches_prefetched = 0
        self.wait_s = 0.0       # the consumer's time blocked in next()
        # the thread holds only a WEAKREF to self (plus the stop event
        # and the queue, which carry no back-reference): a prefetcher
        # abandoned without close() stays collectable, __del__ runs
        # close(), and the worker exits instead of spinning forever
        # with `depth` batches pinned on device
        self._thread = threading.Thread(
            target=_worker_loop,
            args=(weakref.ref(self), self._stop, self._q),
            daemon=True, name="pt-device-prefetch")
        self._thread.start()

    # -- placement (worker thread) ------------------------------------
    def _place_leaf(self, key, v, acc):
        if isinstance(v, Tensor):
            inner = self._place_leaf(key, v._value, acc)
            return Tensor(inner, stop_gradient=v.stop_gradient)
        if not isinstance(v, (np.ndarray, jax.Array)):
            return v           # non-array leaf: the consumer converts
        sh = (self._sharding_for(key, getattr(v, "ndim", 0))
              if self._sharding_for is not None else None)
        if sh is None:
            out = jax.device_put(v)
        elif getattr(v, "sharding", None) == sh:
            out = v                       # already correctly placed
        elif self._needs_global_assembly(sh):
            # multi-process: this host holds only its shard of the
            # global batch; assemble the global array from per-host data
            out = jax.make_array_from_process_local_data(
                sh, np.asarray(v))
        else:
            out = jax.device_put(v, sh)
        acc.append(out)
        return out

    @staticmethod
    def _needs_global_assembly(sh):
        return jax.process_count() > 1 and not sh.is_fully_addressable

    def _place(self, tree, acc, key=None):
        if isinstance(tree, dict):
            return {k: self._place(v, acc, key=k)
                    for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            vals = [self._place(v, acc, key=key) for v in tree]
            if hasattr(tree, "_fields"):      # namedtuple batches
                return type(tree)(*vals)
            return type(tree)(vals)
        return self._place_leaf(key, tree, acc)

    # -- worker --------------------------------------------------------
    def _produce_one(self):
        """Pull + place ONE batch (worker thread); returns a queue item
        (_DONE on source exhaustion)."""
        try:
            batch = next(self._it)
        except StopIteration:
            return _DONE, None
        if chaos.ENABLED:
            chaos.maybe_delay("io.prefetch.delay")
        acc: list = []
        with observability.span("input.h2d"):
            if observability.ENABLED:
                t0 = time.perf_counter()
                placed = self._place(batch, acc)
                for a in acc:         # measure true H2D, not dispatch
                    jax.block_until_ready(a)
                observability.observe("io.h2d.seconds",
                                      time.perf_counter() - t0)
                observability.inc("io.prefetch.batches")
            else:
                placed = self._place(batch, acc)
        self.batches_prefetched += 1
        return _ITEM, placed

    # -- consumer ------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        t0 = time.perf_counter()
        try:
            with observability.span("input.wait"):
                while True:
                    try:
                        tag, payload = self._q.get(timeout=0.1)
                        break
                    except _queue.Empty:
                        if self._stop.is_set() \
                                and not self._thread.is_alive():
                            self._finished = True
                            raise StopIteration from None
        finally:
            self.wait_s += time.perf_counter() - t0
        if observability.ENABLED:
            observability.set_gauge("io.prefetch.queue_depth",
                                    self._q.qsize())
        if tag == _ITEM:
            return payload
        self._finished = True
        if tag == _ERR:
            raise payload
        raise StopIteration                     # _DONE

    def qsize(self) -> int:
        """Batches currently buffered on device (advisory)."""
        return self._q.qsize()

    # -- lifecycle -----------------------------------------------------
    def close(self):
        """Cancel the worker and release the queue. Idempotent; safe
        mid-epoch (remaining prefetched batches are dropped)."""
        self._stop.set()
        try:                   # drain so a producer blocked on a full
            while True:        # queue observes the stop flag promptly
                self._q.get_nowait()
        except _queue.Empty:
            pass
        self._finished = True
        it_close = getattr(self._it, "close", None)
        if it_close is not None:
            try:
                it_close()     # generator sources: run finally blocks
            except Exception:  # lint: disable=silent-swallow -- best-effort generator close at shutdown
                pass           # (incl. 'generator already executing'
            #                    when the worker is inside next())
        if threading.current_thread() is self._thread:
            return             # __del__ fired ON the worker (its own
            #                    wref temporarily revived us): stop is
            #                    set, the loop exits on its own — a
            #                    self-join would raise RuntimeError
        self._thread.join(timeout=5)
        if self._thread.is_alive():
            import warnings
            warnings.warn(
                "DevicePrefetcher.close(): worker did not exit within "
                "5s (the source's next() or a device placement is "
                "still blocking); the daemon thread will exit when it "
                "unblocks", stacklevel=2)
        try:                   # re-drain: a put blocked on the full
            while True:        # queue may have completed into the slot
                self._q.get_nowait()   # the first drain freed
        except _queue.Empty:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            if not self._stop.is_set():
                self.close()
        except Exception:      # lint: disable=silent-swallow -- __del__ during interpreter teardown cannot raise usefully
            pass


def _worker_loop(wref, stop, q):
    """The prefetch thread body. Holds the prefetcher only through
    `wref`, re-checked between batches and between push polls, so an
    abandoned prefetcher (no close(); e.g. an early `break` out of the
    consuming loop) is garbage-collectable — its __del__ runs close()
    and this thread exits promptly either way."""
    while not stop.is_set():
        self = wref()
        if self is None:
            return
        try:
            tag, payload = self._produce_one()
        except BaseException as e:    # noqa: BLE001 — hand to consumer
            tag, payload = _ERR, e
        del self                      # no strong ref while parked below
        while True:                   # bounded-queue push
            if stop.is_set():
                return
            try:
                q.put((tag, payload), timeout=0.05)
                break
            except _queue.Full:
                if wref() is None:
                    return            # consumer abandoned us
                continue
        if tag != _ITEM:
            return                    # exhaustion/error: thread done
        if observability.ENABLED:
            observability.set_gauge("io.prefetch.queue_depth",
                                    q.qsize())


def prefetch_to_device(source, depth=2, *, mesh=None, spec=None,
                       sharding_for=None):
    """Convenience wrapper: `for batch in prefetch_to_device(loader): ...`

    With `mesh` (+ optional `spec`, a PartitionSpec or a callable
    ``(key, ndim) -> PartitionSpec``), every array leaf is placed with
    ``NamedSharding(mesh, spec)`` truncated/padded to its rank — the
    same convention as the trainer's batch placement. Without a mesh,
    leaves land on the default device. Pass `sharding_for` to control
    placement per leaf directly (overrides mesh/spec).

    Training code should prefer ``Trainer.data_iter(loader)``, which
    wires the trainer's own cached shardings in.
    """
    if spec is not None and mesh is None and sharding_for is None:
        raise ValueError(
            "prefetch_to_device: `spec` needs a `mesh` to build a "
            "NamedSharding from — pass mesh= (or sharding_for=); "
            "without it the spec would be silently dropped")
    if sharding_for is None and mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        base = spec if spec is not None else PartitionSpec()
        cache: dict = {}

        def sharding_for(key, ndim):
            sh = cache.get((key, ndim))
            if sh is None:
                s = base(key, ndim) if callable(base) else base
                dims = (tuple(s) + (None,) * ndim)[:ndim]
                sh = NamedSharding(mesh, PartitionSpec(*dims))
                cache[(key, ndim)] = sh
            return sh

    return DevicePrefetcher(source, sharding_for=sharding_for,
                            depth=depth)
