"""Host↔HBM staging: ragged WAL/COPY field bytes → fixed-shape device arrays.

This is the host half of the TPU decode engine. It converts ragged inputs —
pgoutput TupleData values or raw COPY text chunks — into the dense layout the
device kernels consume:

    data     uint8[capacity]      concatenated field bytes (zero-padded)
    offsets  int32[R, C]          start of each field in `data`
    lengths  int32[R, C]          field byte length
    nulls    bool[R, C]           SQL NULL ('n' tuple kind / COPY \\N)
    toast    bool[R, C]           TOAST-unchanged ('u' tuple kind)

Row counts are bucketed to powers of two so jit caches stay small; column
count C is static per schema. The COPY path finds its field boundaries in
one scan of the chunk (native/framer.c `etl_stage_copy_chunk`, the
memchr/SIMD analogue of reference codec/table_row.rs:13-53; vectorized
numpy where the library is not built); rows containing escape sequences
are flagged for the CPU fallback decoder.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from ..models.errors import ErrorKind, EtlError
from ..native import (COPY_STAGE_COUNT, COPY_STAGE_OK, COPY_STAGE_RAGGED,
                      scan_copy_chunk)
from ..postgres.codec.pgoutput import (TUPLE_NULL, TUPLE_TEXT,
                                       TUPLE_UNCHANGED_TOAST, TupleData)

ROW_BUCKETS = (256, 1024, 4096, 16384, 65536, 131072, 262144)


def pad_to_multiple(n: int, multiple: int) -> int:
    """Round `n` up to a multiple of `multiple` (≥1). The mesh decode path
    pads row capacity with all-NULL rows so `sp` sharding engages on
    buckets the device count doesn't divide evenly."""
    if multiple <= 1:
        return n
    return -(-n // multiple) * multiple


def bucket_rows(n: int) -> int:
    """Row-capacity bucket for `n` rows. Staging call sites don't know
    the mesh, so mesh-divisibility padding happens at pack time
    (engine._pack_stage via pad_to_multiple) — sharded dispatch never
    silently rejects a bucket the device count doesn't divide."""
    for b in ROW_BUCKETS:
        if n <= b:
            return b
    return ((n + ROW_BUCKETS[-1] - 1) // ROW_BUCKETS[-1]) * ROW_BUCKETS[-1]


def bucket_pow2(n: int, lo: int = 8, hi: int = 2048) -> int:
    b = lo
    while b < n and b < hi:
        b *= 2
    return b


def bucket_width(n: int, hi: int = 2048) -> int:
    """Field-width bucket: multiples of 4 up to 32 (tight — upload bytes are
    precious over the device link), then powers of two."""
    if n <= 32:
        return max(4, (n + 3) & ~3)
    return bucket_pow2(n, lo=64, hi=hi)


@dataclass
class StagedBatch:
    """Fixed-shape staging of `n_rows` ragged rows × C fields."""

    data: np.ndarray  # uint8[cap]
    offsets: np.ndarray  # int32[R, C]
    lengths: np.ndarray  # int32[R, C]
    nulls: np.ndarray  # bool[R, C]
    toast: np.ndarray  # bool[R, C]
    n_rows: int  # valid rows (R may be larger: bucketed)
    cpu_fallback_rows: np.ndarray = field(
        default_factory=lambda: np.zeros(0, dtype=np.int64))
    # rows needing the exact CPU decoder (escapes, oversized fields)
    copy_escapes: bool = False  # True: field bytes may carry COPY escapes
    # False: the caller forbids publication row-filter compaction on this
    # batch (the assembler clears it for runs carrying updates/deletes or
    # old tuples — client-side filtering covers insert/COPY streams; U/D
    # row-filter transforms are the PG15 walsender's job, docs/decode-
    # pipeline.md). Copy chunks and insert runs keep the default.
    allow_row_filter: bool = True
    # telemetry/spans.py identity of the sealed run / copy chunk these
    # rows came from, carried by every decode span of the batch (0: a
    # batch nobody numbered, e.g. a direct decode() call)
    batch_id: int = 0
    _maxlens: np.ndarray | None = field(default=None, repr=False,
                                        compare=False)

    @property
    def row_capacity(self) -> int:
        return self.offsets.shape[0]

    @property
    def n_cols(self) -> int:
        return self.offsets.shape[1]

    def field_bytes(self, row: int, col: int) -> bytes | None:
        """Raw bytes of one field (CPU fallback path)."""
        if self.nulls[row, col] or self.toast[row, col]:
            return None
        off, ln = int(self.offsets[row, col]), int(self.lengths[row, col])
        return self.data[off : off + ln].tobytes()

    def max_field_len(self, col: int) -> int:
        if self.n_rows == 0:
            return 0
        if self._maxlens is None:
            # one pass over all columns, cached: _widths/_specs/_complete
            # each consult per-column maxima on the hot path
            object.__setattr__(self, "_maxlens",
                               self.lengths[: self.n_rows].max(axis=0))
        return int(self._maxlens[col])

    def gather_rows(self, rows: np.ndarray) -> "StagedBatch":
        """Row-compacted view over the SAME data buffer: the per-row
        bookkeeping arrays gather by `rows` (survivor indices from the
        fused filter's in-program compaction), so the host completion —
        object columns, validity, CPU fixup — runs against the compacted
        index space with zero byte copies."""
        fb = self.cpu_fallback_rows
        if len(fb):
            fb = np.flatnonzero(np.isin(rows, fb)).astype(np.int64)
        return StagedBatch(
            self.data, self.offsets[rows], self.lengths[rows],
            self.nulls[rows], self.toast[rows], len(rows),
            cpu_fallback_rows=fb, copy_escapes=self.copy_escapes,
            allow_row_filter=False, batch_id=self.batch_id)


#: fetch-slice granularity: survivor counts bucket to multiples of
#: max(R/16, 256) so the filtered fetch compiles at most ~16 slice
#: programs per (capacity, layout) while bounding pad slack at ~1/16 of
#: the batch (the "pad slack" term of tests/test_filter_fusion.py
#: ::TestFetchedBytes)
def slice_rows(n: int, capacity: int) -> int:
    if n <= 0:
        return 0
    step = max(256, capacity // 16)
    return min(capacity, -(-n // step) * step)


def stage_tuples(tuples: Sequence[TupleData], n_cols: int) -> StagedBatch:
    """Stage decoded pgoutput tuples. (The zero-copy path that never builds
    TupleData lives in the native framer; this is the portable version.)"""
    n = len(tuples)
    cap_rows = bucket_rows(n)
    offsets = np.zeros((cap_rows, n_cols), dtype=np.int32)
    lengths = np.zeros((cap_rows, n_cols), dtype=np.int32)
    nulls = np.zeros((cap_rows, n_cols), dtype=np.bool_)
    toast = np.zeros((cap_rows, n_cols), dtype=np.bool_)
    nulls[n:, :] = True  # padding rows are all-NULL

    chunks: list[bytes] = []
    pos = 0
    for i, tup in enumerate(tuples):
        if len(tup) != n_cols:
            raise EtlError(ErrorKind.SCHEMA_MISMATCH,
                           f"tuple {i} has {len(tup)} cols, expected {n_cols}")
        for j, (kind, val) in enumerate(zip(tup.kinds, tup.values)):
            if kind == TUPLE_NULL:
                nulls[i, j] = True
            elif kind == TUPLE_UNCHANGED_TOAST:
                toast[i, j] = True
            elif kind != TUPLE_TEXT:
                # binary tuple format is never requested in START_REPLICATION;
                # staging it as text would silently corrupt values
                raise EtlError(ErrorKind.UNSUPPORTED_TYPE,
                               f"tuple {i} col {j}: binary format not enabled")
            else:
                assert val is not None
                offsets[i, j] = pos
                lengths[i, j] = len(val)
                chunks.append(val)
                pos += len(val)
    data = np.frombuffer(b"".join(chunks), dtype=np.uint8) if chunks else \
        np.zeros(0, dtype=np.uint8)
    return StagedBatch(data, offsets, lengths, nulls, toast, n)


def synthetic_staged_batch(n_cols: int, row_capacity: int) -> StagedBatch:
    """An all-NULL staged batch at an exact row capacity: the program-
    store prewarm path decodes one through the engine's own dispatch
    stage so the warmed key, shapes, and dtypes can never drift from
    what production batches of that (schema, bucket) signature use."""
    return StagedBatch(
        np.zeros(0, dtype=np.uint8),
        np.zeros((row_capacity, n_cols), dtype=np.int32),
        np.zeros((row_capacity, n_cols), dtype=np.int32),
        np.ones((row_capacity, n_cols), dtype=np.bool_),
        np.zeros((row_capacity, n_cols), dtype=np.bool_),
        row_capacity)


_NULL_FIELD_BYTES = (92, 78)  # "\\N"


def stage_copy_chunk(chunk: bytes, n_cols: int) -> StagedBatch:
    """Stage a chunk of COPY text rows (newline-terminated): one scan of
    its bytes for the field boundaries, in C where the native library is
    loaded (`native.scan_copy_chunk`) and in numpy where it is not. Rows
    whose fields contain backslash escapes (other than a bare \\N null)
    are routed to `cpu_fallback_rows`."""
    if not chunk:
        return StagedBatch(np.zeros(0, np.uint8), np.zeros((0, n_cols), np.int32),
                           np.zeros((0, n_cols), np.int32),
                           np.zeros((0, n_cols), np.bool_),
                           np.zeros((0, n_cols), np.bool_), 0)
    if not chunk.endswith(b"\n"):
        chunk += b"\n"
    data = np.frombuffer(chunk, dtype=np.uint8)
    scanned = scan_copy_chunk(chunk, n_cols)
    if scanned is None:
        scanned = _scan_copy_chunk_np(data, n_cols)
    status, n_rows, n_delims, offsets, lengths, nulls, fallback = scanned
    # each row must contribute exactly n_cols delimiters (C-1 tabs + 1 nl)
    if status == COPY_STAGE_COUNT:
        raise EtlError(
            ErrorKind.COPY_FORMAT_INVALID,
            f"COPY chunk: {n_delims} delimiters for {n_rows} rows × "
            f"{n_cols} cols")
    if status == COPY_STAGE_RAGGED:
        raise EtlError(ErrorKind.COPY_FORMAT_INVALID,
                       "COPY chunk: ragged rows (tab/newline mismatch)")

    cap_rows = bucket_rows(n_rows)

    def padrc(a, fill=0):
        # in place: the C scan's matrices are cut from the bound they
        # were sized to, the twin's grow by the padding rows
        a.resize((cap_rows, n_cols), refcheck=False)
        a[n_rows:] = fill
        return a

    toast = np.zeros((cap_rows, n_cols), dtype=np.bool_)
    return StagedBatch(data, padrc(offsets), padrc(lengths),
                       padrc(nulls, True), toast, n_rows,
                       cpu_fallback_rows=fallback, copy_escapes=True)


def _scan_copy_chunk_np(data: np.ndarray, n_cols: int):
    """`native.scan_copy_chunk` in numpy, for a process without the
    native library: the same tuple from the same bytes, in some fifteen
    vectorized passes where the C scan takes one."""
    is_tab = data == 9
    is_nl = data == 10
    delim_pos = np.flatnonzero(is_tab | is_nl)
    nl_pos = np.flatnonzero(is_nl)
    n_rows = len(nl_pos)
    if len(delim_pos) != n_rows * n_cols:
        return COPY_STAGE_COUNT, n_rows, len(delim_pos), None, None, None, None
    ends = delim_pos.reshape(n_rows, n_cols)
    if not np.array_equal(ends[:, -1], nl_pos):
        return COPY_STAGE_RAGGED, n_rows, len(delim_pos), None, None, None, None
    starts = np.empty_like(ends)
    starts[:, 0] = np.concatenate(([0], nl_pos[:-1] + 1))
    starts[:, 1:] = ends[:, :-1] + 1
    lengths = (ends - starts).astype(np.int32)
    offsets = starts.astype(np.int32)

    # NULL detection: field == b"\\N"
    first = data[np.minimum(starts, len(data) - 1)]
    second = data[np.minimum(starts + 1, len(data) - 1)]
    nulls = (lengths == 2) & (first == _NULL_FIELD_BYTES[0]) \
        & (second == _NULL_FIELD_BYTES[1])

    # escape detection per row: any backslash in the row span that is not
    # a \N (chunks with no backslash at all — the common case — skip the
    # cumsum, which costs ~5ms/MiB)
    is_bs = data == 92
    if is_bs.any():
        bs_cum = np.concatenate(([0], np.cumsum(is_bs)))
        row_start = starts[:, 0]
        row_end = ends[:, -1]
        bs_in_row = bs_cum[row_end] - bs_cum[row_start]
        nulls_in_row = nulls.sum(axis=1)
        fallback = np.flatnonzero(bs_in_row != nulls_in_row)
    else:
        fallback = np.zeros(0, dtype=np.int64)
    return (COPY_STAGE_OK, n_rows, len(delim_pos), offsets,
            np.where(nulls, 0, lengths), nulls, fallback)


# ---------------------------------------------------------------------------
# staging arenas: reusable pack buffers
# ---------------------------------------------------------------------------


class ArenaLease:
    """The set of pool buffers one in-flight decode holds. `take` hands
    out a pooled (or fresh) array; `release` returns every taken buffer to
    the pool at once — called by the pipeline's fetch stage after the
    device result lands, the earliest point reuse cannot race the
    host→device copy of the batch that packed into them."""

    __slots__ = ("_pool", "_taken", "_released")

    def __init__(self, pool: "StagingArenaPool"):
        self._pool = pool
        self._taken: list[np.ndarray] = []
        self._released = False

    def take(self, shape: tuple, dtype) -> np.ndarray:
        a = self._pool._take(shape, dtype)
        self._taken.append(a)
        return a

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self._pool._give_back(self._taken)
        self._taken = []

    def __enter__(self) -> "ArenaLease":
        return self

    def __exit__(self, *exc) -> None:
        # context-manager form: `with pool.lease() as lease:` releases on
        # every path — the shape etl-lint's arena-lease-leak rule treats
        # as inherently safe
        self.release()


class StagingArenaPool:
    """Preallocated pack-buffer pool, bucketed by (shape, dtype).

    The pack stage writes the byte matrix + lengths (+ nibble bad flags)
    for every batch; with per-batch `np.empty` the allocator churns tens of
    MB per dispatch on the hot loop. Pack shapes are already coarse — row
    capacities are bucketed (ROW_BUCKETS) and gather widths are bucketed
    (bucket_width) — so a handful of arenas per (row_capacity, widths)
    signature covers a steady-state stream, and the bounded in-flight
    window (ops/pipeline.py) caps how many are ever out at once.

    The C packers overwrite every row up to capacity (zero-padding each
    field to its width — framer.c keeps device inputs deterministic), so a
    reused dirty buffer is safe without re-zeroing.
    """

    def __init__(self, max_per_bucket: int = 4):
        self.max_per_bucket = max_per_bucket
        self._lock = threading.Lock()
        self._free: dict[tuple, list[np.ndarray]] = {}
        # buffers handed out and not yet returned: the chaos subsystem's
        # arena-leak invariant reads this before/after a scenario run
        self.outstanding = 0

    def lease(self) -> ArenaLease:
        return ArenaLease(self)

    def _take(self, shape: tuple, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            bucket = self._free.get(key)
            arr = bucket.pop() if bucket else None
            self.outstanding += 1
        from ..telemetry.metrics import (ETL_STAGING_ARENA_REQUESTS_TOTAL,
                                         registry)

        registry.counter_inc(ETL_STAGING_ARENA_REQUESTS_TOTAL, 1.0,
                             {"result": "hit" if arr is not None else "miss"})
        return arr if arr is not None else np.empty(shape, dtype=dtype)

    def _give_back(self, arrays: list[np.ndarray]) -> None:
        with self._lock:
            self.outstanding -= len(arrays)
            for a in arrays:
                key = (a.shape, a.dtype.str)
                bucket = self._free.setdefault(key, [])
                if len(bucket) < self.max_per_bucket:
                    bucket.append(a)

    def stats(self) -> dict:
        with self._lock:
            return {"buckets": len(self._free),
                    "free_arrays": sum(len(v) for v in self._free.values()),
                    "free_bytes": sum(a.nbytes for v in self._free.values()
                                      for a in v),
                    "outstanding": self.outstanding}


#: process-wide pool shared by every decode pipeline (arenas are keyed by
#: exact shape, so cross-table sharing is free and the bound is global)
ARENA_POOL = StagingArenaPool()
