"""Streaming data plane: backpressured Dataset execution over the
zero-copy transfer plane.

Every Dataset runs through this package:

- ``executor``  — operator graph whose submissions are gated by a
  bytes-windowed backpressure budget (per-operator in-flight byte caps,
  stall accounting, spill fallback) instead of task counts alone.
- ``shuffle``   — all-to-all shuffle bundles ride the broadcast/relay
  trees and the range-serve path of the transfer plane instead of N²
  point-to-point pickled gets.
- ``split``     — ack-based streaming split coordinator that re-splits
  on elastic world-size change mid-epoch without dropping or
  duplicating samples.
- ``prefetch``  — pipeline-resident double-buffered host→HBM feed for
  ``iter_jax_batches`` (device_put of batch k+1 overlaps compute on k).
- ``metrics``   — per-operator data-plane gauges federated over the
  report-gauges → syncer → GCS path.
"""
from ray_tpu.data.streaming.executor import streaming_execute
from ray_tpu.data.streaming.prefetch import DevicePrefetcher
from ray_tpu.data.streaming.split import StreamSplitCoordinator

__all__ = [
    "DevicePrefetcher",
    "StreamSplitCoordinator",
    "streaming_execute",
]
