"""Node-side shard reader pipeline: parallel interleave + decode + prefetch.

The DIRECT-input-mode data plane (the reference's ``InputMode.TENSORFLOW``,
per the tf.data paper's parallel-interleave/prefetch design, PAPERS.md):
instead of the driver pumping every row over one socket, each node claims
TFRecord *shard paths* and reads the bytes itself —

    work queue (paths) -> N reader threads -> bounded chunk queue -> consumer
                          read + CRC-verify     (the prefetch buffer)
                          + decode

- **Readers** pull work items — whole shard paths, or ``ShardSpan``
  sub-shard byte ranges of a large plain shard — off the shared work queue
  (tf.data's ``interleave(cycle_length=N)``): plain shards/ranges via one
  IO read + native CRC scan, then ZERO-COPY ``memoryview`` record slices
  (``TOS_INGEST_ZEROCOPY``; no per-record copy between disk and consumer),
  gzip shards via streaming decompression (never a whole-file inflate,
  always ``bytes``).  An optional ``decode`` callable runs per record
  inside the reader thread, so decode parallelism rides reader
  parallelism; a ``schema`` routes records through COLUMNAR Example decode
  instead (``dfutil.decode_span_columns`` — chunks materialize as K
  contiguous column buffers, no per-record parse).
- **The chunk queue is the prefetch buffer** (``TOS_INGEST_PREFETCH``
  chunks deep): readers run ahead of the consumer by up to that many
  decoded chunks, and block (backpressure) beyond it.
- **Autotuned parallelism** (``TOS_INGEST_AUTOTUNE``, tf.data-paper style):
  rather than a fixed thread knob, the consumer's pops sample the queue's
  occupancy — a starving consumer (queue near empty, work pending) grows
  the reader pool toward ``TOS_INGEST_READERS``; a saturated queue shrinks
  it (readers retire at shard boundaries).  Occupancy, pool size, and every
  spawn/retire are exported through ``telemetry``.

``IngestFeed`` (``ingest/feed.py``) drives this pipeline from the node's
feed queue; ``bench_ingest.py`` drives it raw for the scaling numbers.
"""

from __future__ import annotations

import itertools
import logging
import queue
import threading
from tensorflowonspark_tpu.utils.locks import tos_named_lock
import time

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu import tfrecord
from tensorflowonspark_tpu.ingest.shards import ShardSpan
from tensorflowonspark_tpu.utils.envtune import env_bool as _env_bool
from tensorflowonspark_tpu.utils.envtune import env_int as _env_int
from tensorflowonspark_tpu.utils.envtune import env_str as _env_str
from tensorflowonspark_tpu.utils.paths import resolve_uri

logger = logging.getLogger(__name__)

# Autotune thresholds on the occupancy EMA (fraction of queue capacity):
# below LOW with work pending the consumer is starving (grow the pool);
# above HIGH the readers outrun the consumer (shrink it — the threads
# would only block on the full queue anyway).
_TUNE_LOW = 0.25
_TUNE_HIGH = 0.85
_TUNE_INTERVAL_SECS = 0.2
_EMA_ALPHA = 0.3


class ShardReadError(RuntimeError):
    """A reader thread failed on a shard (corrupt CRC, IO error, decode
    bug); re-raised at the consumer with the shard path attached."""


def zerocopy_mode(zerocopy=None) -> str:
    """Resolve a zero-copy setting to ``'on'`` / ``'off'`` / ``'debug'``.

    ``None`` reads ``TOS_INGEST_ZEROCOPY`` (default on); booleans and the
    knob's string values both normalize.  ``debug`` is zero-copy PLUS
    release tracking: the feed releases delivered views when their batch
    retires, so code that retains a view past the documented lifetime gets
    a loud ``ValueError`` instead of silently pinning shard buffers.
    """
    if zerocopy is None:
        zerocopy = _env_str("TOS_INGEST_ZEROCOPY", "1")
    if isinstance(zerocopy, bool):
        return "on" if zerocopy else "off"
    mode = str(zerocopy).strip().lower()
    if mode in ("0", "off", "false", "no"):
        return "off"
    if mode == "debug":
        return "debug"
    return "on"


def _materialize_chunk(chunk):
    """An OWNED copy of one decoded chunk, safe to outlive its shard read:
    ``memoryview`` records become ``bytes``; a ``dfutil.ColumnChunk`` whose
    column arrays view the shard mmap is rebuilt over owning arrays.
    Already-owned chunks (bytes records, owning arrays) copy the list
    head only."""
    import numpy as np

    if hasattr(chunk, "columns") and hasattr(chunk, "counts"):
        cols = {name: (np.array(col, copy=True)
                       if isinstance(col, np.ndarray)
                       and not col.flags.owndata else col)
                for name, col in chunk.columns.items()}
        if all(cols[n] is chunk.columns[n] for n in cols):
            return chunk  # every column already owns its buffer
        clone = type(chunk)(cols, chunk.counts, chunk.n, chunk.scalars,
                            chunk.widths)
        return clone
    return [bytes(r) if type(r) is memoryview else r for r in chunk]


class ShardDone:
    """Control token: every record of one claimed shard has been pushed
    (FIFO) before this token — popping it proves the shard fully drained
    out of the chunk queue.  ``tag`` is the submitter's opaque bookkeeping
    handle (the ingest feed's partition job)."""

    __slots__ = ("path", "tag")

    def __init__(self, path: str, tag=None):
        self.path = path
        self.tag = tag


class _Failure:
    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error


_DRAINED = object()


class ReaderPipeline:
    """Parallel shard readers feeding one bounded decoded-chunk queue.

    Thread roles: ``submit``/``close`` are producer-side (one thread — the
    ingest feed's claimer, or a bench loop); ``get`` is consumer-side (one
    thread — the map_fun via ``IngestFeed``); reader threads are internal.
    """

    def __init__(self, *, readers: int | None = None,
                 autotune: bool | None = None, prefetch: int | None = None,
                 chunk_records: int = 256, decode=None, verify: bool = True,
                 stop_event: threading.Event | None = None,
                 zerocopy=None, schema=None, binary_features=None,
                 cache=None):
        self._max_readers = max(0, readers if readers is not None
                                else _env_int("TOS_INGEST_READERS", 4, minimum=0))
        # Zero-copy decode contract (TOS_INGEST_ZEROCOPY, default ON): plain
        # shards deliver records as MEMORYVIEW slices of the shard buffer —
        # no per-record copy between the disk read and the consumer.  Each
        # view pins the whole buffer, so holders must drop/copy views once
        # their chunk is released (the feed layer defines release as batch
        # retirement); 'off' restores bytes copies, 'debug' releases
        # delivered views so late access fails loudly.  Gzip shards always
        # deliver bytes (stream-decompressed; no stable buffer to view).
        self.zerocopy = zerocopy_mode(zerocopy)
        # Columnar Example decode (schema=...): chunks materialize as
        # dfutil.ColumnChunk — K contiguous column buffers straight from
        # the span scan (native parser when built) instead of per-record
        # parse + per-row repack.  Mutually exclusive with decode= (the
        # schema IS the decoder).
        if schema is not None and decode is not None:
            raise ValueError("schema= and decode= are mutually exclusive: "
                             "columnar decode is driven by the schema")
        self.schema = schema
        self.binary_features = binary_features
        # readers=0: SYNCHRONOUS mode — no reader threads at all, get()
        # reads the next shard inline in the consumer thread (the tf.data
        # ``num_parallel_calls=None`` analogue).  Trades away read/compute
        # overlap for zero cross-thread traffic — the right shape when a
        # node has one core to its name (bench_ingest measures node
        # scale-out in exactly this configuration).
        self._sync = self._max_readers == 0
        self._autotune = (not self._sync) and (
            autotune if autotune is not None
            else _env_bool("TOS_INGEST_AUTOTUNE", True))
        depth = max(1, prefetch if prefetch is not None
                    else _env_int("TOS_INGEST_PREFETCH", 8))
        self.chunk_records = max(1, chunk_records)
        self.decode = decode
        self.verify = verify
        # Cross-epoch chunk cache (ingest/service.py ChunkCache, or any
        # object with get/put/key_for): repeated reads of the same work
        # item + schema serve MATERIALIZED decoded chunks from memory
        # instead of re-running the CRC scan + decode.  Inactive with a
        # per-record ``decode`` callable — its identity cannot be part of
        # the cache key, and serving another decoder's output would be
        # silent corruption.
        self._cache = cache if (cache is not None
                                and getattr(cache, "enabled", True)
                                and decode is None) else None
        # sync mode buffers one whole shard's chunks at a time (get() is
        # both reader and consumer, so a bounded put would self-deadlock)
        self._out: queue.Queue = queue.Queue(maxsize=0 if self._sync else depth)
        self._work: queue.Queue = queue.Queue()  # paths: tiny, unbounded
        self._stop = stop_event if stop_event is not None else threading.Event()
        self._lock = tos_named_lock("readers._lock")
        self._active = 0
        self._target = 1 if self._autotune else self._max_readers
        self._closed = False
        self._drained_pushed = False
        # consumer-side autotune state (touched only from get(); the lock
        # covers the reader-pool fields both sides mutate)
        self._occupancy_ema = 0.0
        self._last_tune = time.monotonic()
        for _ in range(self._target):
            self._spawn_reader_locked()  # pre-publication: no lock needed yet

    # -- producer side -------------------------------------------------------

    def submit(self, path, tag=None) -> None:
        """Queue one work item — a shard path, or a :class:`ShardSpan`
        sub-shard range — for a reader to claim; ``tag`` rides the item's
        ``ShardDone`` token back to the consumer."""
        self._work.put((path, tag))

    def inject(self, payload, tag=None, source=None) -> bool:
        """Producer-side: hand an ALREADY-DECODED chunk (a record list or a
        ``dfutil.ColumnChunk``) straight to the consumer, bypassing the
        readers — how the trainer-side feed consumes chunks a data-service
        worker decoded remotely (``data.DecodedChunk``).  FIFO with the
        work-item bookkeeping: the chunk's ``ShardDone`` follows it
        immediately, so the partition watermark machinery sees each
        forwarded chunk as one fully-drained "shard".  Returns False when
        the pipeline was stopped with the consumer gone."""
        if not self._put(payload):
            return False
        ok = self._put(ShardDone(source if source is not None
                                 else "<forwarded>", tag))
        if ok:
            telemetry.counter("ingest.chunks_injected").inc()
            telemetry.counter("ingest.records_injected").inc(len(payload))
        return ok

    def close(self) -> None:
        """No more shards will be submitted; readers exit as the work queue
        drains, and the consumer sees end-of-pipeline after the last chunk."""
        with self._lock:
            self._closed = True
            # sync mode signals drain via (closed AND work empty) inside
            # _sync_get — pushing the sentinel here would let it overtake
            # still-queued work items
            push = (not self._sync and self._active == 0
                    and not self._drained_pushed)
            if push:
                self._drained_pushed = True
        if push:
            # outside the lock: the put may block on a full prefetch queue,
            # and the consumer needs the lock to drain it (autotune path)
            self._put(_DRAINED)

    def stop(self) -> None:
        """Abandon everything in flight (terminate/stop-signal path)."""
        self._stop.set()

    # -- consumer side -------------------------------------------------------

    def depth(self) -> int:
        """Decoded chunks queued ahead of the consumer (0 in sync mode)."""
        return self._out.qsize()

    def get(self, timeout: float = 0.25):
        """Pop the next item: a list of records (one decoded chunk), a
        :class:`ShardDone` token, or ``None`` once the pipeline has fully
        drained.  Raises ``queue.Empty`` on timeout and
        :class:`ShardReadError` when a reader failed."""
        if self._sync:
            return self._sync_get(timeout)
        self._maybe_tune()
        item = self._out.get(timeout=timeout)
        if item is _DRAINED:
            return None
        if isinstance(item, _Failure):
            raise item.error
        return item

    def _sync_get(self, timeout: float):
        """readers=0: serve buffered chunks, else read the next shard
        INLINE in the calling (consumer) thread."""
        try:
            item = self._out.get_nowait()
        except queue.Empty:  # toslint: allow-silent(no buffered chunk yet: fall through to claim the next shard)
            pass
        else:
            if item is _DRAINED:
                return None
            return item
        if self._stop.is_set():
            return None
        with self._lock:
            closed = self._closed
        if closed:
            # close() precedes no further submits: an empty work queue IS
            # the drain — answer now instead of blocking a full timeout
            # only to discover it (the stall used to add one poll_interval
            # to EVERY sync-mode feed's tail)
            try:
                path, tag = self._work.get_nowait()
            except queue.Empty:
                # observing closed proves every inject() already landed
                # (the claimer injects before calling close, and both
                # sides synchronize on self._lock) — so ONE out-queue
                # re-check closes the race where a chunk was injected
                # between the get_nowait at the top and the closed read
                # above; without it that chunk would be stranded and the
                # feed would report drained with records undelivered
                try:
                    item = self._out.get_nowait()
                except queue.Empty:
                    return None
                return None if item is _DRAINED else item
        else:
            try:
                path, tag = self._work.get(timeout=timeout)
            except queue.Empty:
                with self._lock:
                    closed = self._closed
                if closed:
                    # closed while we were blocked on the (empty) work
                    # queue — but chunks may have been inject()ed into the
                    # out queue during that wait (the pure-consumer feed's
                    # claimer): re-enter from the top, which drains them
                    # before the work-empty check can answer drained
                    return self._sync_get(timeout)
                raise
        try:
            self._read_one(path, tag)
        except Exception as e:  # noqa: BLE001 - same contract as the pool
            wrapped = ShardReadError(f"reading shard {path!r} failed: {e}")
            wrapped.__cause__ = e
            telemetry.counter("ingest.reader_errors").inc()
            raise wrapped from e
        return self._sync_get(timeout)

    def _maybe_tune(self) -> None:
        """Occupancy-EMA autotune, driven by consumer pops (no timer
        thread): grow while the consumer starves, shrink while readers
        saturate the queue.  Sampling at pop time biases toward the moments
        that matter — when the consumer actually wants data."""
        if not self._autotune:
            return
        occupancy = self._out.qsize()
        self._occupancy_ema += _EMA_ALPHA * (occupancy / self._out.maxsize
                                             - self._occupancy_ema)
        now = time.monotonic()
        if now - self._last_tune < _TUNE_INTERVAL_SECS:
            return
        self._last_tune = now
        if (self._occupancy_ema < _TUNE_LOW and not self._work.empty()):
            # closed does NOT gate growth: it only means no more submits,
            # and the work queue may still be deep
            with self._lock:
                if self._target < self._max_readers and self._active > 0:
                    self._target += 1
                    self._spawn_reader_locked()
                    telemetry.counter("ingest.reader_spawns").inc()
        elif self._occupancy_ema > _TUNE_HIGH:
            with self._lock:
                if self._target > 1:
                    self._target -= 1  # a reader retires at its next boundary

    # -- reader pool ---------------------------------------------------------

    def _spawn_reader_locked(self) -> None:
        """Start one reader; caller holds ``self._lock`` (or is __init__,
        pre-publication)."""
        self._active += 1
        telemetry.gauge("ingest.readers_active").set(self._active)
        threading.Thread(target=self._reader_loop, daemon=True,
                         name=f"ingest-reader-{self._active}").start()

    def _reader_loop(self) -> None:
        retired = False
        try:
            while not self._stop.is_set():
                with self._lock:
                    if self._active > self._target:
                        # autotune shrink: exactly one reader retires per
                        # decrement, accounted here so the exit path below
                        # never double-counts (target >= 1, so a retiree is
                        # never the last reader)
                        self._active -= 1
                        retired = True
                        telemetry.counter("ingest.reader_retires").inc()
                        telemetry.gauge("ingest.readers_active").set(self._active)
                        return
                try:
                    path, tag = self._work.get(timeout=0.1)
                except queue.Empty:
                    with self._lock:
                        if self._closed:
                            return
                    continue
                try:
                    self._read_one(path, tag)
                except Exception as e:  # noqa: BLE001 - re-raised consumer-side
                    wrapped = ShardReadError(f"reading shard {path!r} failed: {e}")
                    wrapped.__cause__ = e
                    telemetry.counter("ingest.reader_errors").inc()
                    self._put(_Failure(wrapped))
                    return
        finally:
            if not retired:
                push = False
                with self._lock:
                    self._active -= 1
                    telemetry.gauge("ingest.readers_active").set(self._active)
                    if (self._active == 0
                            and (self._closed or self._stop.is_set())
                            and not self._drained_pushed):
                        self._drained_pushed = True
                        push = True
                if push:
                    # outside the lock (the put can block on a full queue
                    # whose consumer needs the lock); _put gives up only
                    # when stop is set AND the consumer stopped draining,
                    # at which point nobody would read the sentinel anyway
                    self._put(_DRAINED)

    def _read_one(self, item, tag) -> None:
        """Read + verify one work item (whole shard, or a ``ShardSpan``
        sub-shard range), pushing decoded chunks then the item's
        ``ShardDone``.  Plain shards take the span path — ONE open, one
        native CRC scan, then zero-copy ``memoryview`` record slices (or
        bytes copies with ``TOS_INGEST_ZEROCOPY=0``); with ``schema`` set,
        chunks of spans decode columnar (``dfutil.decode_span_columns``)
        into contiguous column buffers instead.  Gzip shards stream (probe
        open + gzip.open) and always deliver bytes."""
        # Cross-epoch chunk cache: a repeated read of the same work item
        # (same bytes, same schema) serves the MATERIALIZED chunks straight
        # from memory — no IO, no CRC scan, no decode.  Misses tee their
        # decoded chunks into the cache on the way out (materialized copies:
        # a cached record must own its buffer, never view a shard mmap that
        # retires with this read).
        tee: dict | None = None
        cache_key = None
        if self._cache is not None:
            cache_key = self._cache.key_for(item, self.schema,
                                            self.binary_features)
            hit = self._cache.get(cache_key)
            if hit is not None:
                nrecs = 0
                for chunk in hit:
                    nrecs += len(chunk)
                    if not self._put(chunk):
                        return  # stopped with the consumer gone
                self._put(ShardDone(item, tag))
                telemetry.counter("ingest.shards_read").inc()
                telemetry.counter("ingest.records_read").inc(nrecs)
                return
            # Tee this read into the cache — UNLESS the item is knowably
            # inadmissible up front (a span bigger than the whole budget):
            # materializing copies that put() would only throw away doubles
            # peak reader memory for zero benefit.  Whole-shard items of
            # unknown decoded size start a tee and abandon it the moment
            # the running byte count crosses the budget (_emit).
            budget = self._cache.max_bytes
            known = (item.end - item.start if isinstance(item, ShardSpan)
                     else None)
            if known is None or known <= budget:
                tee = {"chunks": [], "bytes": 0, "budget": budget}
        # Zero-copy record mode maps the shard instead of read()ing it:
        # the CRC scan and the record views walk page-cache pages
        # directly, saving a full DRAM copy pass per shard — the pass
        # that caps aggregate multi-node ingest of one large shard.
        # Columnar and bytes-copy modes keep the bytes read (their
        # decoders materialize/copy anyway).
        use_map = self.schema is None and self.zerocopy != "off"
        # stage ingest.read: open or map the work item and CRC-scan it (a
        # gzip shard only probes here; it is read as it streams, below)
        with telemetry.stage("ingest.read"):
            if isinstance(item, ShardSpan):
                local = resolve_uri(item.path)
                gz = False
                if use_map:
                    buf, spans = tfrecord.map_span_range(
                        local, item.start, item.end, self.verify)
                else:
                    buf, spans = tfrecord.read_span_range(
                        local, item.start, item.end, self.verify)
            else:
                local = resolve_uri(item)
                buf = None  # stays None for gzip shards (they stream)
                if use_map:
                    # ONE open: gzip probe off the mapped head + CRC scan
                    buf, spans = tfrecord.map_record_spans(local, self.verify)
                    gz = buf is None
                else:
                    with open(local, "rb") as f:
                        gz = tfrecord._is_gzip_shard(f.read(12))
                        if not gz:
                            f.seek(0)
                            buf = f.read()  # one read, no probe+rest concat copy
                    if not gz:
                        spans = tfrecord.scan_record_spans(buf, self.verify,
                                                           name=local)
        if self.schema is not None:
            nrecs, nbytes = self._read_columnar(local, buf,
                                                None if gz else spans, gz,
                                                tee)
            if nrecs is None:
                return  # stopped with the consumer gone
        elif not gz:
            # span fast path: with no decode callable, chunks are plain
            # list windows — no per-record append/accounting loop on the
            # hot path.  Views materialize eagerly (pure slice objects,
            # ~100 ns each, no payload bytes); the BYTES-copy mode slices
            # per window INSIDE the push loop so the bounded prefetch
            # queue keeps pacing the memcpy cost — an eager full-shard
            # copy list would double peak memory per reader.
            zc = self.zerocopy != "off"
            decode = self.decode
            nrecs = len(spans)
            nbytes = sum(length for _, length in spans)
            cr = self.chunk_records
            if decode is None:
                if zc:
                    with telemetry.stage("ingest.decode"):
                        records = tfrecord.record_views(buf, spans)
                for i in range(0, nrecs, cr):
                    if zc:
                        chunk = records[i:i + cr]
                    else:
                        with telemetry.stage("ingest.decode"):
                            chunk = [buf[off:off + length]
                                     for off, length in spans[i:i + cr]]
                    if not self._emit(chunk, tee):
                        return  # stopped with the consumer gone
            else:
                # decode INTERLEAVED with chunk pushes: one chunk's decode
                # cost paces the queue, so the autotuner's pop-time
                # occupancy sampling sees the decode rate, not one
                # end-of-shard burst.  Decode callables keep their
                # PRE-EXISTING bytes contract (bytes() of a bytes slice is
                # the same object; of an mmap view, the one per-record
                # copy — noise next to per-record Python decode): handing
                # views to decoders written against bytes would crash
                # every one of them for no measurable win.
                for i in range(0, nrecs, cr):
                    with telemetry.stage("ingest.decode"):
                        chunk = [decode(bytes(buf[off:off + length]))
                                 for off, length in spans[i:i + cr]]
                    if not self._put(chunk):
                        return
        else:
            payloads = tfrecord.read_records(local, verify=self.verify,
                                             gzipped=True)
            decode = self.decode
            nbytes = 0
            nrecs = 0
            while True:
                # a gzip shard is read as it streams: one chunk's records
                # are pulled (inflate + CRC) under ingest.read
                with telemetry.stage("ingest.read"):
                    chunk = list(itertools.islice(payloads,
                                                  self.chunk_records))
                if not chunk:
                    break
                nrecs += len(chunk)
                nbytes += sum(map(len, chunk))
                if decode is not None:
                    with telemetry.stage("ingest.decode"):
                        chunk = [decode(payload) for payload in chunk]
                if not self._emit(chunk, tee):
                    return  # stopped with the consumer gone
        self._put(ShardDone(item, tag))
        telemetry.counter("ingest.shards_read").inc()
        telemetry.counter("ingest.records_read").inc(nrecs)
        telemetry.counter("ingest.bytes_read").inc(nbytes)
        if tee is not None and tee["chunks"] is not None:
            # the whole item decoded cleanly AND stayed under budget: its
            # materialized chunks are now a cache entry (put re-enforces
            # the byte bound + LRU eviction)
            self._cache.put(cache_key, tee["chunks"], nbytes=tee["bytes"])

    def _emit(self, chunk, tee: dict | None) -> bool:
        """Push one decoded chunk; with the cache teeing this read, append
        a MATERIALIZED copy (owned buffers — zero-copy views die with the
        shard buffer, a cache entry must not).  A tee whose running byte
        count crosses the cache budget is abandoned mid-item — the copies
        are freed immediately instead of riding to an inevitable oversize
        rejection at put()."""
        if tee is not None and tee["chunks"] is not None:
            from tensorflowonspark_tpu.data import chunk_nbytes

            tee["bytes"] += chunk_nbytes(chunk)
            if tee["bytes"] > tee["budget"]:
                tee["chunks"] = None  # inadmissible: stop copying, free now
                telemetry.counter("ingest.cache_oversize_skips").inc()
            else:
                tee["chunks"].append(_materialize_chunk(chunk))
        return self._put(chunk)

    def _read_columnar(self, local: str, buf, spans, gz: bool,
                       tee: list | None = None):
        """Columnar (schema) decode of one work item: every
        ``chunk_records`` spans become ONE ``dfutil.ColumnChunk`` — the
        native parser turns a span window into K contiguous column buffers
        without a per-record Python hop; gzip shards accumulate streamed
        records into the same chunk shape.  Returns ``(nrecs, nbytes)``,
        or ``(None, None)`` when the pipeline stopped mid-item."""
        from tensorflowonspark_tpu import dfutil

        cr = self.chunk_records
        nrecs = 0
        nbytes = 0
        if not gz:
            for i in range(0, len(spans), cr):
                window = spans[i:i + cr]
                with telemetry.stage("ingest.decode"):
                    cols, counts = dfutil.decode_span_columns(
                        buf, window, self.schema, self.binary_features)
                    chunk = dfutil.ColumnChunk.from_schema(cols, counts,
                                                           self.schema)
                if not self._emit(chunk, tee):
                    return None, None
                nrecs += len(window)
                nbytes += sum(length for _, length in window)
            return nrecs, nbytes
        payloads = tfrecord.read_records(local, verify=self.verify,
                                         gzipped=True)
        while True:
            with telemetry.stage("ingest.read"):   # streamed: see _read_one
                batch = list(itertools.islice(payloads, cr))
            if not batch:
                return nrecs, nbytes
            with telemetry.stage("ingest.decode"):
                cols, counts = dfutil.records_to_columns(
                    batch, self.schema, self.binary_features)
                chunk = dfutil.ColumnChunk.from_schema(cols, counts,
                                                       self.schema)
            if not self._emit(chunk, tee):
                return None, None
            nrecs += len(batch)
            nbytes += sum(map(len, batch))

    def _put(self, item) -> bool:
        """Bounded put that stays responsive to stop(): blocking on the full
        prefetch queue IS the backpressure, but an abandoned pipeline (stop
        set, consumer gone) must not strand the reader thread forever.
        Stage ``ingest.put_wait``: time spent here is the reader being
        AHEAD of the consumer, not reading or decoding."""
        with telemetry.stage("ingest.put_wait") as blocked:
            while True:
                try:
                    self._out.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    if self._stop.is_set():
                        return False
                    blocked.tick()
