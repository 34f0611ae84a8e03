"""Pipeline-resident device prefetch for ``iter_jax_batches``.

A ``jax.device_put`` issued inline on the consumer thread serializes
batch formation, host→HBM transfer, and compute.  Here a background
thread owns the whole host side — it pulls numpy
batches from the (already streaming) block iterator, applies the
dtype/sharding transform, and parks up to ``depth`` device-resident
batches in a bounded queue.  With ``depth=2`` (the default knob) the
transfer of batch k+1 overlaps compute on batch k — classic double
buffering (see the tf.data/`jax` host-offload idiom the paper's data
layer describes).

Hit/miss accounting feeds the data-plane gauges: a *hit* means the
consumer found a batch already resident when it asked (the pipeline is
ahead of the accelerator); a run of misses means ingestion is the
bottleneck and shows up as the train cell's ``input_wait_ms``
(``bench/harness/train_cell.py``).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

_SENTINEL = object()


class DevicePrefetcher:
    """Bounded background producer of device-resident batches."""

    def __init__(self, batch_iter: Iterator[Any],
                 to_device: Callable[[Any], Any], *,
                 depth: int = 2, name: str = "train"):
        self._src = batch_iter
        self._to_device = to_device
        self._depth = max(1, depth)
        self._q: queue.Queue = queue.Queue(maxsize=self._depth)
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self.hits = 0
        self.misses = 0
        self._recorded = False
        self._name = name
        self._thread = threading.Thread(
            target=self._run, name=f"data-prefetch-{name}", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        try:
            for batch in self._src:
                dev = self._to_device(batch)
                while not self._stop.is_set():
                    try:
                        self._q.put(dev, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 — surface at consumer
            self._err = e
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(_SENTINEL, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self):
        return self

    def __next__(self):
        try:
            item = self._q.get_nowait()
            self.hits += 1
        except queue.Empty:
            self.misses += 1
            t0 = time.perf_counter()
            item = self._q.get()
            # A blocked get IS the input pipeline stalling the step
            # loop: charge it to the active train session's data_wait
            # phase (no-op outside a training step loop) so
            # StreamingIngest-fed loops get attribution for free.
            try:
                from ray_tpu.train import observability as _tobs

                _tobs.on_data_wait(time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 — accounting must never break
                pass
        if item is _SENTINEL:
            self._record()
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self) -> None:
        """Stop the producer early (consumer abandoned the epoch)."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._record()

    def _record(self) -> None:
        if self._recorded:
            return
        self._recorded = True
        try:
            from ray_tpu.data.streaming import metrics as dm

            dm.on_prefetch(self._name, self.hits, self.misses)
        except Exception:  # noqa: BLE001 — accounting must never break
            pass


def device_prefetching(batch_iter: Iterator[Any], to_device, *,
                       depth: int, name: str = "train") -> Iterator[Any]:
    """Generator wrapper that guarantees producer shutdown when the
    consumer stops early (break out of a partial epoch)."""
    pf = DevicePrefetcher(batch_iter, to_device, depth=depth, name=name)
    try:
        yield from pf
    finally:
        pf.close()
