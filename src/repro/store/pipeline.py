"""Unified panel I/O pipeline: all host->device staging for streamed panels.

Every out-of-core consumer in the core used to own a slice of this logic --
``tile_stream`` hand-rolled a depth-2 double buffer, the oochain GEMM fetched
panels sequentially with no prefetch at all, and the fuse_l chain build did
its own ``device_put`` loop.  :class:`PanelPipeline` owns the pattern once:

* a **background prefetch thread** walks the requested row-panel origins,
  fetching (and codec-decoding -- see :mod:`repro.store.tilestore`) each
  streamed operand's panel on the host, so disk reads and decompression
  overlap device compute;
* **per-operand ring buffers** of configurable depth (default
  ``DEFAULT_PREFETCH_DEPTH`` = 2) bound host staging and give backpressure --
  a slow consumer can never be buried under prefetched panels;
* the consumer-side iterator **stages panels onto devices one origin ahead**
  (the ``device_put`` of panel t+1 is issued before compute on panel t is
  dispatched), preserving the two-panels-per-operand device residency bound
  the streaming executors advertise regardless of the host-side depth;
* **cancellation on early exit**: closing the pipeline (or breaking out of
  the iterator) stops the producer promptly and releases the rings;
* **stats integration**: panels, H2D bytes and peak live device bytes are
  accounted exactly as the old double buffer did, plus the pre-/post-codec
  ``bytes_read`` / ``bytes_decoded`` pair, so ``stream_stats()`` tracks real
  backing-tier traffic.  All counter mutation goes through the stats
  object's atomic ``add`` (registry-backed, see
  :mod:`repro.obs.metrics`), so concurrent producers and a mid-run
  ``reset_stream_stats()`` can no longer lose updates;
* **observability**: the producer accumulates ``pipeline.producer_fetch_seconds``
  and the consumer ``pipeline.consumer_wait_seconds`` in the process metrics
  registry (their ratio is the prefetch-efficiency signal that says whether
  ``depth`` is right).  With tracing enabled, ``pipeline.fetch`` spans each
  panel's fetch and decode on the prefetch thread, ``pipeline.wait`` the
  consumer's wait for it, and ``pipeline.stage`` its pinned-host copy plus
  ``device_put`` (also counted as ``pipeline.stage.seconds`` / ``.calls``);
  ``span_args`` (a request's id, say) tag the consumer's spans;
* **encoded shipping** (``encoded=True``, the stream-GEMM kernel path):
  panels of device-decodable codecs travel in their *stored* form -- bf16
  tiles as raw uint16 bit patterns, half the decoded bytes over H2D, widened
  to fp32 inside the kernel -- with the transfer gap accounted in
  ``bytes_h2d_saved``.  Sources without an encoded read degrade to the
  decoded panel (nothing saved, nothing broken);
* **pinned-host staging** on TPU: staged panels hop through the
  ``pinned_host`` memory space so the H2D copy is an async DMA from pinned
  memory instead of a pageable-numpy transfer.  The choice follows the
  platform of the target sharding -- pinned on TPU, pageable on CPU, where
  the device already is host memory (``pipeline.pinned`` says which).  A
  pinned put that fails on TPU is a staging fault and raises.

Resident ``jax.Array`` operands are *not* routed through the thread: slicing
them is a device-side operation and jax dispatch stays on the consumer
thread.  The producer touches only host objects (numpy, files, codecs).

:class:`CachingHandle` is the iteration-batching companion: it wraps a
snapshot handle with a host-RAM panel cache so a consumer that re-streams the
same matrix (the Richardson solver re-reading P2 every iteration) hits the
backing store once per batch instead of once per pass -- replayed panels are
bitwise identical and report zero ``bytes_read``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Iterator, Sequence

import numpy as np

from repro.obs import timed
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY as _OBS_REGISTRY

DEFAULT_PREFETCH_DEPTH = 2


def _is_handle(x) -> bool:
    """Streamable snapshot handle (duck-typed, mirrors tiles.is_streamable)."""
    return hasattr(x, "read_panel") and hasattr(x, "panel_rows")


def fetch_panel_info(source, row0: int, height: int) -> tuple[np.ndarray, int]:
    """``(host_panel, stored_nbytes)`` for any panel source.

    Handles report their true pre-decode byte count via ``read_panel_info``
    (zero on a :class:`CachingHandle` hit); plain arrays fall back to the
    panel's own size.
    """
    if hasattr(source, "read_panel_info"):
        panel, stored = source.read_panel_info(row0, height)
        return np.asarray(panel), int(stored)
    if _is_handle(source):
        panel = np.asarray(source.read_panel(row0, height))
        return panel, panel.nbytes
    panel = np.asarray(source[row0 : row0 + height])
    return panel, panel.nbytes


def fetch_panel_encoded_info(
    source, row0: int, height: int
) -> tuple[np.ndarray, int, int]:
    """``(panel, stored_nbytes, decoded_nbytes)`` with the panel in its
    device-decodable stored form where the source supports it.

    The stream-GEMM kernel path: a bf16-codec handle returns raw uint16 bit
    patterns (half the decoded bytes; the kernel widens on-device) and
    ``decoded_nbytes`` records what a host-decoded transfer would have
    shipped.  Sources without encoded reads fall back to the decoded panel
    with ``decoded_nbytes == panel.nbytes`` -- nothing saved, same contract.
    """
    if hasattr(source, "read_panel_encoded_info"):
        panel, stored, decoded = source.read_panel_encoded_info(row0, height)
        return np.asarray(panel), int(stored), int(decoded)
    panel, stored = fetch_panel_info(source, row0, height)
    return panel, stored, panel.nbytes


class _Ring:
    """Bounded single-producer/single-consumer ring buffer (one per operand)."""

    def __init__(self, depth: int):
        if depth < 1:
            raise ValueError(f"ring depth must be >= 1, got {depth}")
        self.depth = depth
        self._buf: deque = deque()
        self._cv = threading.Condition()
        self._closed = False

    def put(self, item) -> bool:
        """Block until a slot frees; False once the ring is closed."""
        with self._cv:
            while len(self._buf) >= self.depth and not self._closed:
                self._cv.wait()
            if self._closed:
                return False
            self._buf.append(item)
            self._cv.notify_all()
            return True

    def get(self):
        """Next item, blocking; None once closed (drained items still served)."""
        with self._cv:
            while not self._buf and not self._closed:
                self._cv.wait()
            if self._buf:
                item = self._buf.popleft()
                self._cv.notify_all()
                return item
            return None

    def close(self, *, drain: bool = False) -> None:
        """Stop accepting puts.  ``drain=True`` (producer-error path) keeps
        already-buffered panels poppable, so the consumer still receives
        everything fetched before the fault; ``drain=False`` (consumer
        cancellation) discards them -- nobody will pop."""
        with self._cv:
            self._closed = True
            if not drain:
                self._buf.clear()
            self._cv.notify_all()


class PanelPipeline:
    """Prefetching iterator over row panels of one or more operands.

    Yields ``(row0, panels)`` per origin, in origin order, where ``panels``
    holds one entry per operand.  Operands satisfying the snapshot-handle
    protocol are fetched (and decoded) in the background thread; anything
    else (resident ``jax.Array`` / host array) is sliced lazily on the
    consumer thread, keeping all jax dispatch off the producer.

    ``sharding=None`` yields host panels (the out-of-core GEMM wants the left
    panel on the host for block slicing); with a sharding, each streamed
    panel is ``device_put`` one origin ahead of consumption and the H2D /
    residency counters on ``stats`` are updated exactly as the retired
    double-buffer did.

    ``encoded=True`` ships streamed panels in their device-decodable stored
    form (bf16 -> uint16 bit patterns; see :func:`fetch_panel_encoded_info`)
    for on-device decode by the stream-GEMM kernels; the decoded-vs-stored
    transfer gap is accounted in ``stats.bytes_h2d_saved``.  Device-bound
    panels stage through pinned host memory when the sharding's platform is
    TPU (``pinned``).

    ``span_args`` are added to the consumer's ``pipeline.wait`` and
    ``pipeline.stage`` spans (the read path passes its query id).

    Use as a context manager (or call :meth:`close`) so an early exit --
    consumer exception, solver convergence, test breakage -- cancels the
    producer instead of leaving it blocked on a full ring.
    """

    def __init__(
        self,
        sources: Sequence,
        origins: Sequence[int],
        height: int,
        *,
        depth: int | None = None,
        sharding=None,
        stats=None,
        device_put=None,
        encoded: bool = False,
        span_args: dict | None = None,
    ):
        self.sources = list(sources)
        self.origins = list(origins)
        self.height = int(height)
        self.depth = DEFAULT_PREFETCH_DEPTH if depth is None else int(depth)
        if self.depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {self.depth}")
        self.sharding = sharding
        self.stats = stats
        self._device_put = device_put
        self.encoded = bool(encoded)
        self.span_args = dict(span_args or {})
        self.pinned = (
            sharding is not None
            and next(iter(sharding.device_set)).platform == "tpu"
        )
        self._threaded = [_is_handle(s) for s in self.sources]
        self._rings = [
            _Ring(self.depth) if threaded else None for threaded in self._threaded
        ]
        self._cancel = threading.Event()
        self._error: BaseException | None = None
        self._thread: threading.Thread | None = None
        self.device_live_bytes = 0  # executor-owned panel bytes currently staged
        if any(self._threaded) and self.origins:
            self._thread = threading.Thread(
                target=self._produce, name="panel-prefetch", daemon=True
            )
            self._thread.start()

    # -- producer (background thread: host I/O + codec decode only) ----------

    def _produce(self) -> None:
        try:
            for row0 in self.origins:
                for i, (src, ring) in enumerate(zip(self.sources, self._rings)):
                    if ring is None:
                        continue
                    if self._cancel.is_set():
                        return
                    t_f0 = time.perf_counter()
                    with obs_trace.span("pipeline.fetch", row0=row0, operand=i):
                        if self.encoded:
                            panel, stored, decoded = fetch_panel_encoded_info(
                                src, row0, self.height
                            )
                        else:
                            panel, stored = fetch_panel_info(src, row0, self.height)
                            decoded = panel.nbytes
                    _OBS_REGISTRY.add_named(
                        {
                            "pipeline.producer_fetch_seconds": (
                                time.perf_counter() - t_f0
                            ),
                            "pipeline.panels_fetched": 1.0,
                        }
                    )
                    if self.stats is not None and stored:
                        # stored == 0 means a host-RAM replay (CachingHandle
                        # hit): no backing-tier read, no decode performed.
                        # Encoded panels skip the host decode entirely: the
                        # prefetch thread produced the stored form, which is
                        # exactly panel.nbytes either way.
                        self.stats.add(bytes_read=stored, bytes_decoded=panel.nbytes)
                    if not ring.put((panel, decoded)):
                        return  # closed under us: cancelled
        except BaseException as e:  # propagate to the consumer, then stop
            self._error = e
            self._cancel.set()
            for ring in self._rings:
                if ring is not None:
                    ring.close(drain=True)  # serve what was fetched pre-fault

    # -- consumer ------------------------------------------------------------

    def _next_host_bundle(self, row0: int) -> tuple[list, list]:
        """Panels (+ decoded-byte metadata) for one origin: ring pops for
        handles, lazy slices (decoded == None) for everything else."""
        bundle, decs = [], []
        for src, ring in zip(self.sources, self._rings):
            if ring is None:
                bundle.append(src[row0 : row0 + self.height])
                decs.append(None)
                continue
            t_w0 = time.perf_counter()
            with obs_trace.span("pipeline.wait", row0=row0, **self.span_args):
                item = ring.get()
            _OBS_REGISTRY.inc(
                "pipeline.consumer_wait_seconds", time.perf_counter() - t_w0
            )
            if item is None:
                if self._error is not None:
                    raise RuntimeError(
                        f"panel prefetch failed at row {row0}"
                    ) from self._error
                raise RuntimeError("panel pipeline closed while panels were pending")
            panel, decoded = item
            bundle.append(panel)
            decs.append(decoded)
        return bundle, decs

    def _pin_host(self, panel: np.ndarray):
        """Stage one host panel for its H2D copy: into pinned host memory on
        TPU (errors propagate), as a contiguous pageable array on CPU."""
        panel = np.ascontiguousarray(panel)
        if not self.pinned:
            return panel
        return self._device_put(panel, self.sharding.with_memory_kind("pinned_host"))

    def _stage(self, row0: int) -> tuple[int, list, int]:
        """Fetch/pop one origin's bundle and (optionally) put it on device."""
        bundle, decs = self._next_host_bundle(row0)
        if self.sharding is None:
            return row0, bundle, 0
        staged, nbytes = [], 0
        put = self._device_put
        for panel, decoded, threaded in zip(bundle, decs, self._threaded):
            if threaded:
                with timed("pipeline.stage", row0=row0, **self.span_args):
                    dev = put(self._pin_host(panel), self.sharding)
                nbytes += dev.nbytes
                if self.stats is not None:
                    inc = {"panels": 1, "bytes_h2d": dev.nbytes}
                    if decoded is not None and decoded > dev.nbytes:
                        # Encoded shipping: the gap between what a host-
                        # decoded transfer would have cost and what crossed.
                        inc["bytes_h2d_saved"] = decoded - dev.nbytes
                    self.stats.add(**inc)
                staged.append(dev)
            else:
                staged.append(panel)  # already device-resident; sliced lazily
        return row0, staged, nbytes

    def __iter__(self) -> Iterator[tuple[int, list]]:
        if self._device_put is None and self.sharding is not None:
            import jax  # deferred so host-mode pipelines never touch jax

            self._device_put = jax.device_put
        try:
            if not self.origins:
                return
            if self.sharding is None:
                for row0 in self.origins:
                    yield row0, self._next_host_bundle(row0)[0]
                return
            # Device mode: stage origin t+1 before yielding origin t, so the
            # H2D copy overlaps the compute the consumer dispatches on t.
            prev_row0, prev, prev_bytes = self._stage(self.origins[0])
            for row0 in self.origins[1:]:
                _, cur, cur_bytes = self._stage(row0)
                self.device_live_bytes = prev_bytes + cur_bytes
                if self.stats is not None:
                    self.stats._note_live(self.device_live_bytes)
                yield prev_row0, prev
                prev_row0, prev, prev_bytes = row0, cur, cur_bytes
            self.device_live_bytes = prev_bytes
            if self.stats is not None:
                self.stats._note_live(prev_bytes)
            yield prev_row0, prev
            self.device_live_bytes = 0
        finally:
            self.close()

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Cancel the producer and release the rings (idempotent)."""
        self._cancel.set()
        for ring in self._rings:
            if ring is not None:
                ring.close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "PanelPipeline":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


class CachingHandle:
    """Snapshot-handle wrapper with a host-RAM panel cache (solver batching).

    The Richardson solver re-streams P2 (n^2 bytes) from the scratch store on
    every iteration; wrapping the handle in a :class:`CachingHandle` makes
    iteration batches read the store once and replay the decoded panels from
    host RAM -- bitwise identical panels, ``bytes_read`` counted only on the
    filling pass.  :meth:`refresh` drops the cache (the start of the next
    batch streams from the store again).

    Host cost: up to one full decoded matrix (n^2 x itemsize) while the cache
    is warm -- the premise of a disk-backed scratch is exactly that host RAM
    is the roomier tier.
    """

    def __init__(self, handle):
        if not _is_handle(handle):
            raise TypeError(f"{handle!r} does not satisfy the snapshot-handle protocol")
        self.handle = handle
        self._cache: dict[tuple[int, int], np.ndarray] = {}
        self.fills = 0  # store reads (cache misses)
        self.replays = 0  # cache hits

    @property
    def shape(self):
        return self.handle.shape

    @property
    def dtype(self):
        return self.handle.dtype

    @property
    def nbytes(self):
        return self.handle.nbytes

    @property
    def panel_rows(self) -> int:
        return self.handle.panel_rows

    def refresh(self) -> None:
        """Drop cached panels; the next pass streams from the store again."""
        self._cache.clear()

    def read_panel_info(self, row0: int, height: int) -> tuple[np.ndarray, int]:
        key = (row0, height)
        cached = self._cache.get(key)
        if cached is not None:
            self.replays += 1
            return cached, 0  # zero backing-store bytes: a host-RAM replay
        panel, stored = fetch_panel_info(self.handle, row0, height)
        self._cache[key] = panel
        self.fills += 1
        return panel, stored

    def read_panel_encoded_info(
        self, row0: int, height: int
    ) -> tuple[np.ndarray, int, int]:
        """Encoded (stored-form) read with the same replay semantics.

        Cached separately from decoded panels -- a consumer mixing both read
        forms (the kernel-path solver after an XLA-path chi build) must never
        replay a decoded fp32 panel where uint16 bits were requested.
        """
        key = (row0, height, "enc")
        cached = self._cache.get(key)
        if cached is not None:
            self.replays += 1
            panel, decoded = cached
            return panel, 0, decoded  # host-RAM replay: no backing-store bytes
        panel, stored, decoded = fetch_panel_encoded_info(self.handle, row0, height)
        self._cache[key] = (panel, decoded)
        self.fills += 1
        return panel, stored, decoded

    def read_panel(self, row0: int, height: int) -> np.ndarray:
        return self.read_panel_info(row0, height)[0]
