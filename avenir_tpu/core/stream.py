"""Chunked streaming CSV ingest: the 1B-row scale path.

The reference streams unbounded HDFS files through mappers one line at a
time (bayesian/BayesianDistribution.java:137 map() sees a single line; no
job ever holds an input split in memory). The TPU-native analog is block
streaming: read fixed-size byte blocks, cut at the last newline, columnar-
parse each block (native C++ single pass when built — native/csv_ingest.cpp)
and hand the algorithm a sequence of Dataset chunks whose sufficient
statistics it folds in. Count algebra is additive (NaiveBayesModel.
accumulate/merge, Markov bigram counts, Apriori supports), so chunked
ingest changes nothing about the result — host RSS stays O(block), not
O(file), which is what makes the BASELINE.md 1B-row metric physically
reachable on one host.

`prefetched()` overlaps host parsing of block k+1 with device compute on
block k in a daemon thread — the map/compute overlap Hadoop gets from
running mappers concurrently with the shuffle, without the shuffle.
"""

from __future__ import annotations

import os
import queue
import re
import threading
from typing import Iterable, Iterator, Optional, Tuple, TypeVar

from avenir_tpu import obs as _obs
from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureSchema

DEFAULT_BLOCK_BYTES = 64 << 20
#: default queued-items depth of the outer prefetched() job feeds — the
#: `stream.prefetch.depth` conf key overrides it per job (the autotuner
#: moves it from measured stall attribution; analysis/mem.py prices the
#: blocks-in-flight terms from the same number)
DEFAULT_PREFETCH_DEPTH = 2
# first non-whitespace byte, located without copying the block the way
# bytes.strip() would (pattern.search scans the buffer in place)
_NONWS = re.compile(rb"\S")

T = TypeVar("T")


class CsvBlockReader:
    """Iterate Dataset chunks of a CSV file without loading it whole.

    Blocks are `block_bytes` of file data extended to the next newline;
    every chunk parses against the *same* schema object, so dictionary
    codes stay consistent across chunks (data-discovered vocabularies
    extend in place — see dataset._discover_cardinality)."""

    def __init__(self, path: str, schema: FeatureSchema, delim: str = ",",
                 block_bytes: int = DEFAULT_BLOCK_BYTES, engine: str = "auto",
                 keep_raw: bool = False,
                 byte_range: Optional[Tuple[int, int]] = None):
        """byte_range=(start, end) restricts the reader to one INPUT SPLIT
        of the file with the Hadoop LineRecordReader boundary contract
        (the multi-host ingest analog of an HDFS split): a split starting
        mid-line skips forward past its first newline (the previous split
        owns that line), and a split owns every line that STARTS before
        `end` — reading past `end` to finish the boundary line. Covering
        [0, size) with disjoint ranges therefore yields every line exactly
        once."""
        if not os.path.exists(path):
            raise FileNotFoundError(f"no such CSV file: {path!r}")
        if block_bytes < 1:
            raise ValueError(f"block_bytes must be positive, got {block_bytes}")
        if byte_range is not None:
            s, e = byte_range
            if s < 0 or e < s:
                raise ValueError(f"invalid byte_range {byte_range}")
        self.path = path
        self.schema = schema
        self.delim = delim
        self.block_bytes = block_bytes
        self.engine = engine
        self.keep_raw = keep_raw
        self.byte_range = byte_range

    def __iter__(self) -> Iterator[Dataset]:
        # one copy of the split-boundary algorithm: the byte blocks come
        # from iter_byte_blocks (same LineRecordReader contract), parsed
        # against the shared schema. The block read runs in a prefetch
        # thread so file IO overlaps the native parse (a ctypes call
        # releases the GIL) on multi-core hosts
        # depth=1: one block ahead is all the IO/parse overlap needs, and
        # it caps the raw bytes in flight at ~2 x block_bytes (jobs stack
        # an outer prefetched() of parsed Datasets on top of this)
        for blk in prefetched(iter_byte_blocks(self.path, self.block_bytes,
                                               self.byte_range), depth=1):
            yield self._parse(blk)

    def _parse(self, chunk: bytes) -> Dataset:
        t0 = _obs.now()
        ds = Dataset.from_csv(chunk, self.schema, delim=self.delim,
                              engine=self.engine, keep_raw=self.keep_raw)
        _obs.record("stream.parse", t0, path=self.path, nbytes=len(chunk),
                    rows=len(ds))
        return ds


def iter_csv_chunks(path: str, schema: FeatureSchema, delim: str = ",",
                    block_bytes: int = DEFAULT_BLOCK_BYTES,
                    engine: str = "auto",
                    keep_raw: bool = False) -> Iterator[Dataset]:
    """Yield Dataset chunks of `path`; a small file yields one chunk."""
    return iter(CsvBlockReader(path, schema, delim, block_bytes, engine,
                               keep_raw))


_DONE = object()

#: Audit/test hook: when set, called with no arguments once per item a
#: prefetched() worker produces (before the queue put). The chunk-
#: invariance auditor (analysis/flow.py) installs a deterministic-jitter
#: scheduler here to prove streamed folds don't depend on producer
#: timing, and a counting hook to prove chunk layouts actually differ.
#: Production leaves it None; the check is one load per block.
_produce_hook = None

#: Byte-accounting hook: when set, called with the byte size of every
#: bytes-like item a prefetched() worker produces (0 for non-bytes
#: items, which carry their own accounting). The memory auditor
#: (analysis/mem.py) installs a recorder here to prove the footprint
#: model's block-size term against the blocks that actually flowed —
#: the stream layer's half of the RSS oracle. Production leaves it
#: None; the check is one load per block.
_bytes_hook = None


def _item_nbytes(item) -> int:
    """Accountable byte size of a produced item: RAW byte blocks only —
    bare, or (offset, block) pairs from iter_byte_blocks' with_offsets
    mode (the delta-scan feeds). Parsed/encoded items (Datasets, padded
    pages, packed bitsets) are priced by the footprint model's own
    per-job terms, so counting them here would double-book them against
    the raw-block term."""
    if isinstance(item, (bytes, bytearray, memoryview)):
        return len(item)
    if isinstance(item, tuple) and len(item) == 2 \
            and isinstance(item[1], (bytes, bytearray, memoryview)):
        return len(item[1])
    return 0

#: consumer-side poll granularity: bounds how long a pull can block
#: before re-checking that the worker is still alive (a dead worker with
#: an empty queue would otherwise hang the consumer forever)
_GET_POLL_SECS = 0.5
#: close() bound on joining the worker; a worker alive past this is
#: wedged in `items` (e.g. blocking IO) and is reported, not ignored
_JOIN_SECS = 10.0


def _prefetch_worker(items: Iterable, q: "queue.Queue",
                     cancel: threading.Event, error_cell: list) -> None:
    """Producer body. Deliberately a MODULE function taking its state as
    arguments: a bound-method target would make the worker thread keep
    its own _Prefetcher alive, so an abandoned iterator could never be
    garbage-collected (and its worker never cancelled) while the worker
    ran — the leak the join contract exists to prevent."""

    def put(item) -> bool:
        # producer-stall attribution: time blocked on a FULL queue means
        # the CONSUMER (device fold / downstream parse) is the
        # bottleneck for this item — the dual of the consumer-stall
        # span in _Prefetcher.__next__
        t0 = _obs.now()
        while not cancel.is_set():
            try:
                q.put(item, timeout=0.1)
                _obs.record_min("stream.stall.producer", t0,
                                nbytes=_item_nbytes(item))
                return True
            except queue.Full:
                continue
        return False

    it = iter(items)
    try:
        for item in it:
            hook = _produce_hook
            if hook is not None:
                hook()
            bhook = _bytes_hook
            if bhook is not None:
                bhook(_item_nbytes(item))
            if not put(item):
                break
        else:
            put(_DONE)
    except BaseException as exc:  # re-raised on the consumer side
        error_cell[0] = exc       # kept even if the queue put loses a
        put(exc)                  # race with close(): never dropped
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


class _Prefetcher(Iterator[T]):
    """Iterator over `items` produced by a background worker thread.

    The consumer contract prefetched() documents lives here: order
    preserved, worker exceptions re-raise at the consumer's next pull,
    and close() — called explicitly, by `yield from` delegation, on
    exhaustion, or at GC — cancels AND JOINS the worker so its thread
    and any file handle inside `items` never outlive the consumer. A
    worker exception that the consumer has not yet pulled re-raises from
    an explicit close() instead of being dropped."""

    def __init__(self, items: Iterable[T], depth: int):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._cancel = threading.Event()
        self._error_cell: list = [None]
        self._thread: Optional[threading.Thread] = threading.Thread(
            target=_prefetch_worker,
            args=(items, self._q, self._cancel, self._error_cell),
            daemon=True)
        self._thread.start()

    def __iter__(self) -> "_Prefetcher":
        return self

    def __next__(self) -> T:
        if self._thread is None:
            raise StopIteration
        # consumer-stall attribution: time blocked on an EMPTY queue
        # means the PRODUCER (disk read / parse worker) is the
        # bottleneck for this pull
        t0 = _obs.now()
        while True:
            try:
                item = self._q.get(timeout=_GET_POLL_SECS)
            except queue.Empty:
                if not self._thread.is_alive() and self._q.empty():
                    # every worker exit path posts _DONE or an exception;
                    # an empty queue with a dead worker means the process
                    # is tearing down — fail crisply instead of hanging
                    self.close()
                    raise RuntimeError(
                        "prefetch worker exited without a result")
                continue
            if item is _DONE:
                self.close()
                raise StopIteration
            if isinstance(item, BaseException):
                self._error_cell[0] = None   # delivered: close() must
                self.close(_suppress=True)   # not re-raise it
                raise item
            _obs.record_min("stream.stall.consumer", t0,
                            nbytes=_item_nbytes(item))
            return item

    def close(self, _suppress: bool = False) -> None:
        """Cancel the worker, join it, and re-raise any worker exception
        the consumer never pulled (unless `_suppress`, used on the paths
        where the exception is already propagating)."""
        thread, self._thread = self._thread, None
        if thread is None:
            return
        self._cancel.set()
        # drain so a worker blocked on a full queue sees the cancel fast
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        thread.join(_JOIN_SECS)
        if thread.is_alive():
            raise RuntimeError(
                f"prefetch worker failed to stop within {_JOIN_SECS}s "
                f"(wedged inside its source iterable?)")
        pending, self._error_cell[0] = self._error_cell[0], None
        if pending is not None and not _suppress:
            raise pending

    def __del__(self):
        try:
            self.close(_suppress=True)   # GC close never raises
        except Exception:
            pass


def prefetched(items: Iterable[T], depth: int = 2) -> Iterator[T]:
    """Run `items` in a background worker thread, keeping up to `depth`
    results queued ahead of the consumer. Exceptions re-raise at the
    consumer's next pull; order is preserved. The returned iterator's
    close() (also invoked by abandonment/GC) cancels AND joins the worker
    — so its thread and any file handle inside `items` don't outlive the
    consumer — and propagates a worker exception the consumer never saw."""
    return _Prefetcher(items, depth)


def double_buffered(items: Iterable[T]) -> Iterator[T]:
    """Depth-1 prefetch: host production of block k+1 overlaps consumption
    (device counting) of block k, and at most ONE finished block waits in
    the queue — the bounded-RSS flavor of prefetched() the multi-pass
    miners put between chunk encode/pack and the device support fold.
    Stacks safely on the inner byte-block prefetch: the pipeline then
    holds one block being read, one being encoded, one being counted."""
    return prefetched(items, depth=1)


class SharedScan:
    """ONE disk read + ONE parse per chunk, fanned out to N fold sinks.

    The scan-sharing executor: every streamed job used to make its own
    full pass over the same corpus (nb + mi + discriminant each re-read
    and re-parsed the multi-GB churn CSV), so ingest cost — the measured
    limiter once folds are vectorized — multiplied with the job count.
    Here the chunk iterator (typically a prefetched() CSV/byte-block
    reader) runs ONCE and each produced chunk is handed to every
    registered sink in registration order, sequentially — fold order per
    sink is exactly the order the one-job-one-scan path would see, which
    is what makes shared-scan outputs byte-identical to per-job scans
    (asserted by the chunk-invariance auditor's fused entries).

    Error contract: a sink raising mid-scan closes the underlying
    iterator before the exception propagates — for a prefetched() feed
    that cancels AND joins the worker thread (the PR-4 _Prefetcher join
    guarantee), so a failing consumer never wedges or leaks the
    producer. Generator feeds built on ``yield from prefetched(...)``
    (stream_job_inputs and friends) delegate close() the same way."""

    def __init__(self, chunks: Iterable):
        self._chunks = chunks
        self._sinks: list = []

    def add_sink(self, sink, label: Optional[str] = None) -> None:
        """Register a per-chunk consumer: any callable taking one chunk
        (or an object with a ``consume`` method). `label` names the
        sink in its per-chunk ``stream.fold`` spans (default: the
        sink's class/function name)."""
        fn = getattr(sink, "consume", sink)
        if label is None:
            label = (type(sink).__name__ if hasattr(sink, "consume")
                     else getattr(sink, "__name__", "sink"))
        self._sinks.append((fn, label))

    def run(self) -> int:
        """Drive the scan: one pull per chunk, every sink sees it.
        Returns the number of chunks scanned. Each sink call records a
        ``stream.fold`` span and every chunk's full fan-out feeds the
        process-global ``chunk_latency_ms`` histogram."""
        n = 0
        it = iter(self._chunks)
        try:
            for chunk in it:
                t_chunk = _obs.now()
                for sink, label in self._sinks:
                    t0 = _obs.now()
                    sink(chunk)
                    _obs.record("stream.fold", t0, sink=label, chunk=n)
                _obs.observe("chunk_latency_ms",
                             (_obs.now() - t_chunk) * 1e3)
                n += 1
        except BaseException:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()          # join the worker; the sink's (or
                except Exception:    # producer's) exception is already
                    pass             # propagating — don't mask it
            raise
        else:
            close = getattr(it, "close", None)
            if close is not None:
                close()
        return n


def prefetch_depth(cfg) -> int:
    """The `stream.prefetch.depth` conf key (default 2, floor 1): how
    many produced items may queue ahead of the consumer in the outer
    job feeds below. Deeper absorbs producer burstiness when the
    consumer measurably waits (the autotuner's signal); every queued
    item is a resident parsed chunk / raw block, which is why the
    footprint model's in-flight terms scale with this same number."""
    return max(int(cfg.get_float("stream.prefetch.depth",
                                 float(DEFAULT_PREFETCH_DEPTH))), 1)


def _sidecar_payloads(feed) -> Iterator:
    """Drop the (offset, length, hash) bookkeeping of a sidecar feed and
    the blank-block placeholders — what job consumers fold."""
    for _off, _length, _hash, payload in feed:
        if payload is not None:
            yield payload


def stream_job_inputs(cfg, inputs: Iterable[str], schema: FeatureSchema,
                      keep_raw: bool = False) -> Iterator[Dataset]:
    """Per-job streaming input helper: prefetched block chunks of every
    input path, sized by the `stream.block.size.mb` config key (default
    64) and queued `stream.prefetch.depth` deep. The one way runner
    jobs consume CSV inputs at unbounded size.

    When the columnar sidecar can engage (native parse path, single-byte
    delimiter, `stream.sidecar` not disabled), each path streams through
    native.sidecar.dataset_blocks instead: a verified repeat scan
    replays packed binary columns parse-free, a cold scan parses AND
    packs, and any doubt — absent manifest, content drift, torn write —
    falls back to the cold chunks below, byte-identically."""
    block = int(cfg.get_float("stream.block.size.mb", 64.0) * (1 << 20))
    depth = prefetch_depth(cfg)
    sc = sc_opts = None
    if not keep_raw:
        try:
            from avenir_tpu.native import sidecar as sc

            sc_opts = sc.opts_from_cfg(cfg)
        except Exception:
            sc_opts = None
    for path in inputs:
        feed = None
        if sc_opts is not None:
            feed = sc.dataset_blocks(sc_opts, path, schema,
                                     cfg.field_delim_regex, block)
        if feed is not None:
            yield from prefetched(_sidecar_payloads(feed), depth=depth)
        else:
            yield from prefetched(iter_csv_chunks(
                path, schema, cfg.field_delim_regex, block,
                keep_raw=keep_raw), depth=depth)


def iter_byte_blocks(path: str,
                     block_bytes: int = DEFAULT_BLOCK_BYTES,
                     byte_range: Optional[Tuple[int, int]] = None,
                     with_offsets: bool = False) -> Iterator:
    """Yield ~block_bytes raw byte blocks cut at line boundaries — the
    zero-copy feed for native block consumers (seq_encode): no decode,
    no per-line Python strings.

    byte_range=(start, end) restricts to one INPUT SPLIT with the same
    Hadoop LineRecordReader boundary contract as CsvBlockReader: a split
    starting mid-line skips past its first newline (the previous split
    owns that line) and owns every line that STARTS before `end`, so
    disjoint ranges covering [0, size) yield every line exactly once —
    multi-host ingest for the sequence jobs.

    with_offsets=True yields (offset, block) pairs instead, where
    `offset` is the ABSOLUTE file offset of the block's first byte, and
    whitespace-only blocks are yielded too so consecutive blocks tile
    the covered range gap-free — the delta-scan drivers (the incremental
    runner, the encoded-block cache's per-block fingerprints) account
    for every covered byte; consumers skip folding blank blocks
    themselves (folds treat them as zero rows anyway). The default mode
    keeps the historical contract: bare blocks, blanks dropped."""
    blocks = _offset_byte_blocks(path, block_bytes, byte_range)
    if with_offsets:
        return blocks
    return _blank_filtered(blocks)


def _blank_filtered(blocks: Iterator[Tuple[int, bytes]]) -> Iterator[bytes]:
    nonblank = _NONWS.search   # no-copy emptiness check (strip() copies)
    try:
        for _off, blk in blocks:
            if nonblank(blk):
                yield blk
    finally:
        blocks.close()          # abandonment closes the file promptly


def _offset_byte_blocks(path: str, block_bytes: int,
                        byte_range: Optional[Tuple[int, int]]
                        ) -> Iterator[Tuple[int, bytes]]:
    """(absolute offset, block) pairs tiling the byte range gap-free —
    the one copy of the split-boundary block cutter behind both
    iter_byte_blocks modes."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"no such input file: {path!r}")
    if block_bytes < 1:
        raise ValueError(f"block_bytes must be positive, got {block_bytes}")
    if byte_range is not None:
        s, e = byte_range
        if s < 0 or e < s:
            raise ValueError(f"invalid byte_range {byte_range}")
    size = os.path.getsize(path)
    start, end = byte_range if byte_range else (0, size)
    end = min(end, size)
    with open(path, "rb") as fh:
        if start > 0:
            fh.seek(start - 1)
            if fh.read(1) != b"\n":
                fh.readline()
        pos = fh.tell()
        emit = pos               # offset of the next unemitted byte
        carry = b""
        # per-block read spans: t_blk opens when assembly of the next
        # emitted block starts (reset after every yield, so consumer
        # time between pulls is never billed to the read)
        t_blk = _obs.now()
        while pos < end:
            block = fh.read(block_bytes)
            if not block:
                break
            pos += len(block)
            if pos >= end:
                # finish the line containing byte end-1 (we own every
                # line starting before `end`), reading past end if its
                # newline isn't buffered yet
                data = carry + block if carry else block
                carry = b""
                b = len(data) - (pos - end)
                if b > 0 and data[b - 1:b] == b"\n":
                    cut = b
                else:
                    nl = data.find(b"\n", b)
                    while nl < 0:
                        extra = fh.read(block_bytes)
                        if not extra:
                            break
                        off = len(data)
                        data += extra
                        nl = data.find(b"\n", off)
                    cut = (nl + 1) if nl >= 0 else len(data)
                _obs.record("stream.read", t_blk, path=path, offset=emit,
                            nbytes=cut)
                yield emit, data[:cut]
                return
            # carry never contains a newline, so the cut within `block`
            # is the cut within carry+block — splice with ONE copy
            # (join reads the memoryview; no intermediate slice bytes)
            cut = block.rfind(b"\n")
            if cut < 0:
                carry += block
                continue
            out = (b"".join((carry, memoryview(block)[:cut + 1]))
                   if carry else block[:cut + 1])
            carry = block[cut + 1:]
            _obs.record("stream.read", t_blk, path=path, offset=emit,
                        nbytes=len(out))
            yield emit, out
            emit += len(out)
            t_blk = _obs.now()
        if carry:
            _obs.record("stream.read", t_blk, path=path, offset=emit,
                        nbytes=len(carry))
            yield emit, carry


def split_byte_ranges(total: int, n: int) -> list:
    """`n` contiguous [lo, hi) ranges tiling ``[0, total)`` gap-free —
    the ONE copy of the input-split arithmetic behind every multi-process
    ingest surface (``parallel.multihost.host_shard_bounds``, the shard
    planner's nominal block bounds). Ceil-division sizing, so a total
    smaller than the split count yields trailing EMPTY ranges that still
    tile (``(total, total)``) — consumers built on the LineRecordReader
    boundary contract (``iter_byte_blocks``/``CsvBlockReader`` with
    ``byte_range=``) then see zero lines for those, never a duplicated
    or dropped boundary line. Pinned by the edge regression tests in
    tests/test_stream.py (no trailing newline, single-line corpus,
    corpus smaller than the split count)."""
    if n < 1:
        raise ValueError(f"split count must be positive, got {n}")
    if total < 0:
        raise ValueError(f"total must be non-negative, got {total}")
    per = (total + n - 1) // n
    ranges = []
    for i in range(n):
        lo = min(i * per, total)
        ranges.append((lo, min(lo + per, total)))
    return ranges


def is_blank_block(data: bytes) -> bool:
    """True when a raw byte block holds no non-whitespace byte — the
    no-copy check delta-scan drivers use to skip folding the blank
    blocks that with_offsets mode must still account for."""
    return _NONWS.search(data) is None


def iter_line_blocks(path: str,
                     block_bytes: int = DEFAULT_BLOCK_BYTES
                     ) -> Iterator[list]:
    """Yield lists of non-empty text lines, ~block_bytes of file each.

    The untyped-row analog of CsvBlockReader for jobs whose input is not
    schema-typed CSV (sequence files, transaction lists, free text): the
    reference streams those one line at a time through the same mapper
    contract (e.g. markov/MarkovStateTransitionModel.java:116-133,
    association/FrequentItemsApriori.java:138-150); here the unit is a
    block of lines, so host RSS stays O(block) however large the file."""
    for blk in iter_byte_blocks(path, block_bytes):
        lines = [ln.rstrip("\r")
                 for ln in blk.decode("utf-8", "replace").split("\n")
                 if ln.strip()]
        if lines:
            yield lines


def stream_job_lines(cfg, inputs: Iterable[str]) -> Iterator[list]:
    """Prefetched line blocks of every input path, sized by the same
    `stream.block.size.mb` key (and queued `stream.prefetch.depth`
    deep) as stream_job_inputs."""
    block = int(cfg.get_float("stream.block.size.mb", 64.0) * (1 << 20))
    depth = prefetch_depth(cfg)
    for path in inputs:
        yield from prefetched(iter_line_blocks(path, block), depth=depth)


def stream_job_byte_blocks(cfg, inputs: Iterable[str],
                           sidecar_skip: Optional[int] = None
                           ) -> Iterator[bytes]:
    """Prefetched raw byte blocks of every input path (the native
    seq_encode feed), sized by the same `stream.block.size.mb` key and
    queued `stream.prefetch.depth` deep.

    `sidecar_skip` OPTS IN to the bytes-kind columnar sidecar: callers
    whose consumers dispatch on native.sidecar.SidecarBytesBlock (the
    CSR folds — markov fit_csr, the miner scan sinks) pass their meta-
    column skip count, and verified repeat scans then replay packed
    codes instead of raw text. Callers that fold raw bytes directly
    leave it None and keep the historical feed."""
    block = int(cfg.get_float("stream.block.size.mb", 64.0) * (1 << 20))
    depth = prefetch_depth(cfg)
    sc = sc_opts = None
    if sidecar_skip is not None:
        try:
            from avenir_tpu.native import sidecar as sc

            sc_opts = sc.opts_from_cfg(cfg)
        except Exception:
            sc_opts = None
    for path in inputs:
        feed = None
        if sc_opts is not None:
            feed = sc.byte_blocks(sc_opts, path, cfg.field_delim_regex,
                                  int(sidecar_skip), block)
        if feed is not None:
            yield from prefetched(_sidecar_payloads(feed), depth=depth)
        else:
            yield from prefetched(iter_byte_blocks(path, block),
                                  depth=depth)
