"""DeviceDecoder: the TPU decode engine (`batch_engine=tpu`).

Pipeline per batch (north star in BASELINE.json):

  StagedBatch (host, ops/staging.py)
    → host pack: vectorized numpy gather of all dense-column field bytes
      into ONE [R, ΣW] byte matrix (minimizes host↔device transfer: only
      bytes the device parses are uploaded, in one array)
    → device: one jitted program per (row-bucket, width-signature) parsing
      every dense column (ops/parsers.py) and emitting ONE bit-packed
      uint32[n_words, R] result (ops/bitpack.py: per row, each column's
      ok bit + components at text-width-bounded offsets — one fetch of
      the fewest bytes, whatever the device→host link turns out to cost)
    → host: exact numpy combines into int64/f64 columns
    → CPU-oracle fallback decode for flagged rows (escapes, BC dates,
      17-digit floats, oversized fields) — mixed batches partition,
      they never fail
    → ColumnarBatch (typed columnar + validity + TOAST masks)

`decode_async` dispatches without blocking so the host stages batch N+1
while the device works on batch N (the software-pipelining analogue of the
reference's one-in-flight flush, apply.rs:1956-2023).

Object-typed columns (text, uuid, json, bytea, numeric-as-text, arrays,
intervals) are materialized host-side — strings via a vectorized Arrow
gather, no per-row Python objects.

Reference parity: replaces the per-tuple `parse_cell_from_postgres_text`
hot loop (crates/etl/src/postgres/codec/text.rs) behind the same batching
boundary the reference flushes at (apply.rs:1910-1948).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.annotations import dispatch_stage, hot_loop
from ..models.pgtypes import CellKind
from ..models.schema import ReplicatedTableSchema
from ..models.table_row import Column, ColumnarBatch, dense_dtype
from ..postgres.codec.text import parse_cell_text
from . import parsers
from .staging import (ArenaLease, StagedBatch, bucket_pow2, bucket_width,
                      pad_to_multiple)

# kinds parsed on device; everything else is host-object
DEVICE_KINDS = frozenset({
    CellKind.BOOL, CellKind.I16, CellKind.I32, CellKind.U32, CellKind.I64,
    CellKind.F32, CellKind.F64, CellKind.DATE, CellKind.TIME,
    CellKind.TIMESTAMP, CellKind.TIMESTAMPTZ,
})

# minimum gather widths: enough for the parsers' static column indexing
# (clipped gathers make larger fields safe — they fall back via the
# oversize check); kept tight because upload bytes are the binding
# resource on the device link
_MIN_WIDTH = {
    CellKind.DATE: 16,
    CellKind.TIME: 16,
    CellKind.TIMESTAMP: 32,
    CellKind.TIMESTAMPTZ: 32,
    CellKind.F32: 16,
    CellKind.F64: 16,
}
MAX_FIELD_WIDTH = 2048  # beyond this a field goes to CPU fallback

# fixed gather widths for the HOST-backend program: wide enough for every
# in-range text of the kind (longer → CPU fallback, same as the device
# oversize rule), so the jit signature is data-INDEPENDENT — one compile
# per (schema, row bucket) instead of one per drifting width signature.
# Host memory traffic is cheap; only the device link makes widths precious.
_HOST_WIDTH = {
    CellKind.BOOL: 4,
    CellKind.I16: 8,          # "-32768"
    CellKind.I32: 12,         # "-2147483648"
    CellKind.U32: 12,
    CellKind.I64: 20,         # "-9223372036854775808"
    CellKind.F32: 32,         # "-1.7976931348623157e+308" is 24
    CellKind.F64: 32,
    CellKind.DATE: 16,
    CellKind.TIME: 16,        # "HH:MM:SS.ffffff"
    CellKind.TIMESTAMP: 32,   # date + space + time = 26
    CellKind.TIMESTAMPTZ: 36, # + "+15:59:59"
}

def round_up_even(n: int) -> int:
    return (n + 1) & ~1

# kinds whose text always fits the 15-symbol nibble alphabet (framer.c):
# digits, sign, dot, colon, space. BOOL ('t'/'f') doesn't; neither do
# floats — PG prints |v| ≥ 1e15 or < 1e-4 in exponent form ('5e-05'),
# which would flag whole rows for CPU fallback, so float columns keep the
# raw byte path.
_NIBBLE_KINDS = frozenset({
    CellKind.I16, CellKind.I32, CellKind.U32, CellKind.I64,
    CellKind.DATE, CellKind.TIME,
    CellKind.TIMESTAMP, CellKind.TIMESTAMPTZ,
})


@dataclasses.dataclass(frozen=True)
class _ColSpec:
    index: int  # position among replicated columns
    kind: CellKind


@dataclasses.dataclass
class _PackedInputs:
    """Output of the pack stage, input of the dispatch stage.
    `row_capacity` may exceed the staged capacity (mesh padding rows,
    zeroed); the fn-cache key and device shapes use it. `row_flags`
    (uint8[row_capacity], fused-filter dispatches only) carries the
    host's per-row disposition: 0 dead padding / 1 live / 2 live +
    force-keep (escapes, nibble-flagged, oversized or TOASTed
    predicate-referenced field — device values untrustworthy, the host
    re-evaluates those survivors after oracle fixup)."""

    bmat: np.ndarray
    lengths: np.ndarray
    nibble: bool
    bad_rows: np.ndarray | None
    row_capacity: int
    use_mesh: bool
    row_flags: np.ndarray | None = None
    filtered: bool = False
    # the canonical layout this batch packed into (program_store.
    # canonical_plan): dispatch keys and builds the program from
    # plan.specs, completion unpacks each real column from its canonical
    # slot. None on the fused-filter path (predicates bind staged column
    # indices, so those programs stay exact).
    plan: "object | None" = None
    # in-flight device egress output (ops/egress.py): (ebytes, elens,
    # EgressPlan) attached by the dispatch stage when the decoder has a
    # wire encoder bound; completion fetches and indexes it per schema
    # column. None = no device egress for this batch (cold program,
    # filtered dispatch, non-renderable layout) — destinations fall back
    # to the host twins.
    egress: "tuple | None" = None


def build_device_program(specs: tuple[tuple[int, CellKind, int, int], ...],
                         nibble: bool = False,
                         n_shards: int | None = None,
                         pred=None):
    """The (unjitted) single-chip forward step for one width-signature.

    Inputs:  bmat u8[R, ΣW] packed field bytes (or u8[R, ΣW/2] nibble pairs
             when `nibble` — two 4-bit symbols per byte, unpacked on device
             through a 16-entry table back to ASCII so the parsers are
             identical), lengths i32[R, n_dense]
    Output:  uint32[n_words, R] bit-packed per ops/bitpack.build_layout —
             each row's ok bits + components in the fewest words their
             text-width-bounded magnitudes allow. ONE array, minimal
             bytes: one device→host fetch per batch.
             With `n_shards` (the mesh-sharded path) the program ALSO
             returns int32[n_shards] per-shard fallback-candidate counts,
             reduced on device inside each row shard (bitpack.
             parse_and_pack) — 4 bytes per shard of extra fetch, and the
             host learns shard health without unpacking anything.
             With `pred` (predicate.CompiledRowFilter) the program takes a
             third input (row_flags uint8[R]) and returns the FUSED
             coerce→filter→pack result: (words_compacted, keep_mask,
             counts[, shard_bad]) — survivors compacted to the front of
             their shard block so the host fetch is sized by the survivor
             count, not the batch size.

    specs: (col_index, kind, gather_width, bit_width) per dense column.
    """
    from .bitpack import parse_and_pack

    if pred is not None:
        def fn(bmat, lengths, row_flags):
            return parse_and_pack(bmat, lengths.astype(jnp.int32), specs,
                                  nibble, n_shards=n_shards, pred=pred,
                                  row_flags=row_flags)

        return name_program(fn, "etl_decode_filter")

    def fn(bmat, lengths):
        return parse_and_pack(bmat, lengths.astype(jnp.int32), specs, nibble,
                              n_shards=n_shards)

    return name_program(fn, "etl_decode")


def name_program(fn: Callable, name: str) -> Callable:
    """Name a program body before `jax.jit` sees it: the device trace's
    module line then reads `jit_<name>` instead of `jit_fn`, so decode,
    filter and egress device time can be told apart (no compiled code
    changes; persistent-cache keys do, once)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


# jitted decode programs shared across ALL DeviceDecoder instances (one
# is created per table and per copy partition; without sharing, each
# re-pays the 10-40s XLA/Mosaic compile for an identical program).
# Bounded LRU: long-running processes with schema churn must not pin
# executables for dropped tables forever — past the cap the
# least-RECENTLY-USED entry is evicted (hits refresh recency via
# move_to_end, so a hot program can't be popped by churn in cold ones;
# worst case: a rare recompile, never a leak). The lock covers lookup and
# eviction: the pipeline's dispatch stage runs on worker threads, and a
# torn OrderedDict relink would corrupt the cache for every decoder.
_SHARED_FN_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_SHARED_FN_CACHE_MAX = 64
_SHARED_FN_LOCK = threading.Lock()


def _shared_fn_get(key: tuple) -> Callable | None:
    with _SHARED_FN_LOCK:
        fn = _SHARED_FN_CACHE.get(key)
        if fn is not None:
            _SHARED_FN_CACHE.move_to_end(key)
        return fn


def _shared_fn_put(key: tuple, fn: Callable) -> None:
    with _SHARED_FN_LOCK:
        _SHARED_FN_CACHE[key] = fn
        _SHARED_FN_CACHE.move_to_end(key)
        while len(_SHARED_FN_CACHE) > _SHARED_FN_CACHE_MAX:
            _SHARED_FN_CACHE.popitem(last=False)


# keys whose host program is compiling on a background thread right now:
# `_route` keeps sending matching batches to the oracle until the compile
# lands, so neither the triggering batch nor its followers block on jax's
# per-signature compile lock
_BG_COMPILE_KEYS: set = set()
# keys whose background build raised: decode stays on the oracle for the
# stream's lifetime rather than respawning a doomed compile thread (and
# re-logging) on every subsequent batch of that signature
_BG_COMPILE_FAILED: set = set()
_BG_COMPILE_LOCK = threading.Lock()


def _host_fn_key(row_capacity: int, specs: tuple,
                 pred_fp: "tuple | None" = None) -> tuple:
    """The module-level program-cache key of the HOST decode path for one
    (row bucket, specs) signature: host packs force nibble compression
    off, never shard on the mesh, and never select pallas. `pred_fp` is
    the fused row filter's fingerprint (None = unfiltered program — a
    different output STRUCTURE, so the keys must never collide). The
    dispatch stage builds its keys through this same helper, so the probe
    in `_host_fn_ready` can never drift from the cache it is probing.
    Callers pass EXACT specs; the key carries their CANONICAL layout
    (program_store.canonical_plan) so every schema that shares a layout
    shares the key. The engine flag stays the LAST element
    (routing-proof tests key on key[-1])."""
    if pred_fp is None and specs:
        from . import program_store

        specs = program_store.canonical_plan(specs).specs
    return (row_capacity, specs, False, None, False, pred_fp, True)


def _host_fn_ready(decoder: "DeviceDecoder", staged: "StagedBatch",
                   specs: tuple) -> bool:
    """True when the host program for this (bucket, specs) is compiled and
    callable without blocking. On a cold key, start the build+compile on a
    background thread (executing the decoder's own dispatch path against
    the triggering batch, so the key and shapes match exactly) and report
    not ready."""
    pred = decoder._device_filter_for(staged)
    key = _host_fn_key(staged.row_capacity, specs,
                       pred.fingerprint() if pred is not None else None)
    with _BG_COMPILE_LOCK:
        if key in _BG_COMPILE_KEYS or key in _BG_COMPILE_FAILED:
            return False
        if _shared_fn_get(key) is not None:
            return True
    # disk probe BEFORE conceding to the oracle: a restarted process
    # finds the executable the previous incarnation compiled and loads
    # it inline (sub-second even for wide schemas) — the warm-restart
    # path that makes restart cost I/O, not XLA (ops/program_store.py).
    # record_absent=False: a miss here flows into the background
    # build's acquire(), which probes and counts the same key again
    from . import program_store

    fn = program_store.try_load(key, record_absent=False)
    if fn is not None:
        _shared_fn_put(key, fn)
        return True
    with _BG_COMPILE_LOCK:
        if key in _BG_COMPILE_KEYS or key in _BG_COMPILE_FAILED:
            return False
        _BG_COMPILE_KEYS.add(key)

    def work() -> None:
        try:
            value, _ = decoder._device_call(staged, specs, host=True)
            jax.block_until_ready(value)
        except Exception:
            import logging

            with _BG_COMPILE_LOCK:
                _BG_COMPILE_FAILED.add(key)
            logging.getLogger("etl_tpu.ops").warning(
                "background host-program compile failed; batches of this "
                "signature keep decoding on the oracle", exc_info=True)
        finally:
            with _BG_COMPILE_LOCK:
                _BG_COMPILE_KEYS.discard(key)

    from ..telemetry.metrics import (ETL_DECODE_BACKGROUND_COMPILES_TOTAL,
                                     registry)

    registry.counter_inc(ETL_DECODE_BACKGROUND_COMPILES_TOTAL)
    # non-daemon: a daemon thread killed mid-XLA-build at interpreter
    # teardown aborts the whole process from C++ ("terminate called
    # without an active exception"); non-daemon means process exit joins
    # an in-flight compile instead — rare in practice, compiles happen in
    # a stream's first seconds
    try:
        threading.Thread(target=work, name="etl-decode-bg-compile",
                         daemon=False).start()
    except RuntimeError:
        # thread limit / interpreter shutdown: work()'s finally never runs,
        # so release the key here and pin the signature to the oracle
        # rather than raising into the decode path
        with _BG_COMPILE_LOCK:
            _BG_COMPILE_KEYS.discard(key)
            _BG_COMPILE_FAILED.add(key)
    return False


def background_compiles_inflight() -> int:
    """How many host-program builds are currently running on background
    threads. Bench warmups poll this to zero before opening a measured
    window — otherwise the window measures the transient oracle-fallback
    period instead of the warm steady state."""
    with _BG_COMPILE_LOCK:
        return len(_BG_COMPILE_KEYS)


def _donation_supported() -> bool:
    """Buffer donation is implemented on TPU/GPU only; on the CPU backend
    jax warns per call and keeps both buffers alive, so donating there
    buys nothing and spams logs."""
    return jax.default_backend() in ("tpu", "gpu")


_ACCEL_BACKEND: "bool | None" = None


def accelerator_backend() -> bool:
    """True when jax's default backend is a real accelerator (TPU/GPU).
    Cached — the backend choice is fixed per process. Gates policies that
    only pay off with a device across the transfer link: backlog
    mega-batching grows seals to clear the DEVICE routing threshold, but
    on the host-CPU backend every grown bucket is a fresh multi-hundred-ms
    XLA compile and a larger host program — measured 5× WORSE end-to-end
    streaming (~41k vs ~200k ev/s) than staying at the standard seal."""
    global _ACCEL_BACKEND
    if _ACCEL_BACKEND is None:
        _ACCEL_BACKEND = jax.default_backend() in ("tpu", "gpu")
    return _ACCEL_BACKEND


def _build_device_fn(specs, nibble: bool = False, use_pallas: bool = False,
                     mesh=None, donate: bool = False, pred=None):
    # donate_argnums on the packed inputs: XLA reuses the uploaded bmat /
    # lengths device buffers for scratch or output, so a steady pipelined
    # stream stops accumulating one dead input buffer per in-flight batch
    # in HBM. Host-side numpy arenas are unaffected (the donated buffer is
    # the DEVICE copy), so arena reuse stays safe.
    kw = {"donate_argnums": (0, 1)} if donate else {}
    if mesh is not None:
        # multi-chip: rows sharded over the 'sp' axis, the SAME program —
        # decode is elementwise over rows, so XLA partitions it with no
        # cross-device collectives on the forward path; the bit-packed
        # output keeps its row shards until the host fetch gathers them,
        # and the per-shard fallback-candidate counts stay sharded too
        # (one i32 per device). The packed staging buffers are donated
        # (TPU/GPU) exactly as on the single-device path — donation is
        # per-shard, so each device reuses its own input block. The fused
        # row filter compacts PER SHARD (bitpack.compact_packed reshapes
        # exactly along the block sharding), so survivor scatter stays
        # shard-local too; rowids and per-shard survivor counts come back
        # row-sharded.
        from jax.sharding import NamedSharding, PartitionSpec as P

        rows_sharded = NamedSharding(mesh, P("sp", None))
        out_sharded = NamedSharding(mesh, P(None, "sp"))
        shard_red = NamedSharding(mesh, P("sp"))
        if pred is not None:
            rows_1d = NamedSharding(mesh, P("sp"))
            return jax.jit(
                build_device_program(specs, nibble, n_shards=mesh.size,
                                     pred=pred),
                in_shardings=(rows_sharded, rows_sharded, rows_1d),
                out_shardings=(out_sharded, rows_1d, shard_red, shard_red),
                **kw)
        return jax.jit(build_device_program(specs, nibble,
                                            n_shards=mesh.size),
                       in_shardings=(rows_sharded, rows_sharded),
                       out_shardings=(out_sharded, shard_red), **kw)
    if use_pallas:
        from .pallas_kernel import build_pallas_program

        return jax.jit(build_pallas_program(specs, nibble, pred=pred), **kw)
    return jax.jit(build_device_program(specs, nibble, pred=pred), **kw)


def program_example_avals(specs, row_capacity: int, nibble: bool = False,
                          pred=None) -> tuple:
    """ShapeDtypeStructs matching exactly what the dispatch stage passes
    for one (specs, row bucket) signature: bmat u8[R, ΣW] (halved under
    nibble packing), lengths u8/i32[R, n] per the pack stage's dtype rule,
    plus the row_flags u8[R] disposition vector on the fused-filter path.
    The IR lint tier lowers programs from these instead of staging real
    batches — shapes/dtypes ARE the jit signature, so the lowering can
    never drift from what production dispatches compile."""
    widths = tuple(w for _, _, w, _ in specs)
    total_w = sum(widths)
    bmat = jax.ShapeDtypeStruct(
        (row_capacity, total_w // 2 if nibble else total_w), np.uint8)
    ldtype = np.uint8 if max(widths, default=0) <= 255 else np.int32
    lengths = jax.ShapeDtypeStruct((row_capacity, len(specs)), ldtype)
    if pred is not None:
        return (bmat, lengths,
                jax.ShapeDtypeStruct((row_capacity,), np.uint8))
    return (bmat, lengths)


def lower_program(specs, row_capacity: int, *, nibble: bool = False,
                  use_pallas: bool = False, mesh=None, donate: bool = False,
                  pred=None):
    """Lower one decode program WITHOUT compiling it to an executable:
    returns (jitted, example_avals, jax.stages.Lowered). This is the IR
    tier's single entry into the engine — the same `_build_device_fn`
    constructor every dispatch path uses, so the jaxpr/StableHLO the
    contracts inspect is the jaxpr/StableHLO production compiles."""
    fn = _build_device_fn(specs, nibble, use_pallas, mesh=mesh,
                          donate=donate, pred=pred)
    avals = program_example_avals(specs, row_capacity, nibble, pred)
    return fn, avals, fn.lower(*avals)


def _combine(kind: CellKind, rows: np.ndarray) -> np.ndarray:
    """Exact host-side combine of packed device rows (ordered per
    parsers.COLUMN_COMPONENTS) into the column dtype."""
    if kind is CellKind.BOOL:
        return rows[0].astype(np.bool_)
    if kind in (CellKind.I16, CellKind.I32, CellKind.U32):
        return rows[0].astype(dense_dtype(kind))
    if kind is CellKind.I64:
        neg, l0, l1, l2 = rows
        v = (l2.astype(np.int64) * 10**18 + l1.astype(np.int64) * 10**9
             + l0.astype(np.int64))
        return np.where(neg != 0, -v, v)
    if kind in (CellKind.F32, CellKind.F64):
        neg, l0, l1, ea, sp = rows
        m = (l1.astype(np.int64) * 10**9 + l0.astype(np.int64)) \
            .astype(np.float64)
        ea = ea.astype(np.int64)
        v = np.where(ea >= 0, m * np.power(10.0, np.clip(ea, 0, 22)),
                     m / np.power(10.0, np.clip(-ea, 0, 22)))
        v = np.where(neg != 0, -v, v)
        v = np.where(sp == 1, np.nan, v)
        v = np.where(sp == 2, np.inf, v)
        v = np.where(sp == 3, -np.inf, v)
        return v.astype(dense_dtype(kind))
    if kind is CellKind.DATE:
        return rows[0].astype(np.int32)
    if kind is CellKind.TIME:
        return rows[0].astype(np.int64) * 1000 + rows[1].astype(np.int64)
    if kind in (CellKind.TIMESTAMP, CellKind.TIMESTAMPTZ):
        days, ms, us = rows
        return (days.astype(np.int64) * 86_400_000_000
                + ms.astype(np.int64) * 1000 + us.astype(np.int64))
    raise AssertionError(kind)


class _PendingDecode:
    """Handle for an in-flight device decode; `result()` completes it.
    The device→host copy of the packed result is started at construction
    (`copy_to_host_async`), so the transfer rides the link while the host
    stages and packs the next batches — `result()` mostly finds the bytes
    already landed. Mesh-sharded dispatches carry a tuple; every value
    starts its host copy here — EXCEPT the fused-filter single-device
    case, where only the 4-byte survivor COUNT pre-fetches: the packed
    words and rowids are fetched at `result()` as a count-sized slice, so
    the device→host link carries survivor bytes, not batch bytes (the
    fetch-reduction half of the fused-filter win)."""

    __slots__ = ("_decoder", "_staged", "_specs", "_packed", "_meta",
                 "_done")

    def __init__(self, decoder: "DeviceDecoder", staged: StagedBatch,
                 specs: tuple, packed, meta: "_PackedInputs | None" = None):
        self._decoder = decoder
        self._staged = staged
        self._specs = specs
        self._packed = packed
        self._meta = meta
        self._done: ColumnarBatch | None = None
        filtered = meta is not None and meta.filtered
        if filtered and not meta.use_mesh and isinstance(packed, tuple):
            # keep mask (1 bit/row) + counts only; the words fetch is a
            # count-sized device slice at result()
            values = packed[1:3]
        else:
            values = packed if isinstance(packed, tuple) else (packed,)
        for v in values:
            if v is not None:
                try:
                    v.copy_to_host_async()
                except AttributeError:
                    pass  # non-jax array (tests may inject numpy)
        if meta is not None and meta.egress is not None:
            # wire bytes + lengths ride the link alongside the packed
            # words; completion finds them landed
            for v in meta.egress[:2]:
                try:
                    v.copy_to_host_async()
                except AttributeError:
                    pass

    @property
    def survivors(self) -> "np.ndarray | None":
        """Original staged-row indices of the rows the completed batch
        kept, or None for an unfiltered decode. Valid after result()."""
        batch = self.result()
        return getattr(batch, "source_rows", None)

    def result(self) -> ColumnarBatch:
        if self._done is None:
            self._done = self._decoder._complete(
                self._staged, self._specs, self._packed,
                self._meta.bad_rows if self._meta is not None else None,
                meta=self._meta)
        return self._done


@functools.lru_cache(maxsize=None)
def host_cpu_device():
    """The host CPU backend's device. Present even when the default
    backend is a TPU — XLA's CPU client is built in, so the SAME decode
    program can execute host-side for batches too small to amortize the
    accelerator round trip. A process whose JAX_PLATFORMS names the
    accelerator alone has no such client: that is a deployment error
    (`Pipeline.start` surfaces it), not a reason to decode every
    sub-threshold batch on the per-row oracle."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "no CPU backend in this process: the decode engine runs "
            "sub-threshold batches on XLA:CPU next to the accelerator "
            "(JAX_PLATFORMS must include 'cpu', e.g. 'tpu,cpu', or be "
            "unset)") from e


# -- supervision degrade hook -------------------------------------------------

# monotonic deadline until which EVERY decoder routes to the host oracle
# (supervision escalation after repeated device-side stalls); process-
# global on purpose: a sick device link is a process-level condition,
# like the per-process autotune cost model
_ORACLE_FORCED_UNTIL = 0.0


def force_host_oracle(duration_s: float) -> None:
    """Route all decode batches to the host oracle for `duration_s`."""
    import time

    global _ORACLE_FORCED_UNTIL
    _ORACLE_FORCED_UNTIL = time.monotonic() + duration_s


def clear_forced_oracle() -> None:
    global _ORACLE_FORCED_UNTIL
    _ORACLE_FORCED_UNTIL = 0.0


def host_oracle_forced() -> bool:
    import time

    return _ORACLE_FORCED_UNTIL > 0.0 \
        and time.monotonic() < _ORACLE_FORCED_UNTIL


class DeviceDecoder:
    """Schema-bound batch decoder. Jitted programs live in the
    module-level _SHARED_FN_CACHE keyed by (row_capacity, specs, nibble,
    mesh, pallas, host) — shared across instances; each decoder keeps a
    record of the keys it used (`_fn_cache`) for compile-count tests."""

    # below this row count the device round trip is taken to lose to the
    # host paths: small CDC flushes decode on host, WAL bursts and copy
    # partitions go to the device. The static default only routes where
    # ops/autotune has no measurement (CPU-only processes) or its margin
    # is non-positive; the value is not measured on this machine
    DEVICE_MIN_ROWS = 131_072

    # CDC flush runs (hundreds of rows between commit barriers) are far
    # below DEVICE_MIN_ROWS; at/above this row count they run the SAME
    # XLA decode program on the host CPU backend — one vectorized dispatch
    # instead of a per-row Python oracle pass (~100× on the streaming hot
    # path). Below it, dispatch overhead loses to the oracle.
    HOST_MIN_ROWS = 64

    # below this row count a multi-device mesh buys nothing (per-shard
    # work too small vs dispatch overhead); batches at/above it shard rows
    # across 'sp' (SURVEY §7: data-parallel decode across ragged batches)
    MESH_MIN_ROWS = 65_536

    def __init__(self, schema: ReplicatedTableSchema, *,
                 numeric_mode: str = "text", use_pallas: bool = False,
                 device_min_rows: int | None = None,
                 host_min_rows: int | None = None,
                 mesh: "object | str | None" = "auto",
                 mesh_min_rows: int | None = None,
                 telemetry: bool = True,
                 nonblocking_compile: bool = False,
                 egress: "str | None" = None):
        self.schema = schema
        self.use_pallas = use_pallas
        # wire encoder name (ops/egress.py ENCODER_*) when the bound
        # destination consumes device-rendered text; decoded batches then
        # carry `device_egress` buffers next to their columns
        self.egress = egress
        # streaming decoders (assembler / copy) must never block a worker
        # on a first-touch XLA build: a 120-column host program compiles
        # for tens of seconds (measured 32s on this container), which
        # freezes apply progress past the stall deadline and sends the
        # supervision watchdog into a cancel→re-stream→re-wedge loop.
        # With nonblocking_compile the cold (bucket, specs) batch decodes
        # on the oracle while the program compiles on a background
        # thread; warm batches route to the host program as usual.
        self.nonblocking_compile = nonblocking_compile
        # telemetry=False keeps synthetic decodes (the autotune host-rate
        # probe) out of the routed-rows/decode counters so the device-share
        # metric reflects real replication traffic only
        self._telemetry = telemetry
        self.host_min_rows = self.HOST_MIN_ROWS \
            if host_min_rows is None else host_min_rows
        if mesh == "auto":
            from ..parallel.mesh import default_decode_mesh

            mesh = default_decode_mesh()
        self.mesh = mesh  # jax.sharding.Mesh | None
        self.mesh_min_rows = self.MESH_MIN_ROWS \
            if mesh_min_rows is None else mesh_min_rows
        cols = schema.replicated_columns
        self._numeric_mode = numeric_mode
        self._dense: list[_ColSpec] = []
        self._object: list[_ColSpec] = []
        for i, c in enumerate(cols):
            kind = c.kind
            if kind is CellKind.NUMERIC and numeric_mode == "f64":
                kind = CellKind.F64
            if kind in DEVICE_KINDS:
                self._dense.append(_ColSpec(i, kind))
            else:
                self._object.append(_ColSpec(i, kind))
        if len(self._dense) > 250:
            # the C packer handles 256 columns; beyond 250 dense device
            # columns the tail spills to the host-object path (the byte
            # matrix for such tables is bounded by the batch size budget,
            # not the column count)
            for spec in self._dense[250:]:
                self._object.append(spec)
            self._dense = self._dense[:250]
        # column counts for the cell counters (telemetry/metrics.py),
        # taken once here so the increments multiply two integers
        self.n_columns = len(cols)
        self.n_device_kind_columns = len(self._dense)
        # publication row filter: compiled ONCE here (etl-lint rule 13
        # flags compile_row_filter on @hot_loop paths — a per-batch
        # compile would re-bind literals and re-trace per flush). An
        # unparseable/unbindable filter degrades to None with a warning:
        # the batch then decodes unfiltered, which is only correct when
        # the server still filters — the pipeline logs loudly so the
        # offload deployment can't silently deliver excluded rows.
        self._row_filter = None
        rf = getattr(schema, "row_predicate", None)
        if rf is not None:
            from .predicate import RowFilterError, compile_row_filter

            try:
                self._row_filter = compile_row_filter(rf, schema)
            except RowFilterError:
                import logging

                logging.getLogger("etl_tpu.ops").warning(
                    "row filter %r on %s is outside the client-side "
                    "envelope; decoding UNFILTERED (server-side filtering "
                    "must cover this table)", getattr(rf, "sql", rf),
                    schema.name, exc_info=True)
        # record of the programs THIS decoder used (tests pin per-
        # decoder compile-count invariants on it); the fns themselves
        # live in the module-level _SHARED_FN_CACHE
        self._fn_cache: dict[tuple, Callable] = {}
        # computed eagerly: a decoder is shared between the event loop
        # and warm_host_programs' executor thread, and an init-before-
        # spawn write is the one publication order that needs no lock
        # (the lazy fill here was the concurrency tier's first real
        # unsynchronized-shared-mutation finding)
        self._host_specs_cache: tuple = self._compute_host_specs()
        if device_min_rows is not None:
            self.device_min_rows = device_min_rows
        else:
            # measured, not hardcoded (VERDICT r4 #1a): solve the
            # host-vs-device crossover from the probed link cost model
            # and this schema's actual per-row traffic (gather widths up,
            # packed words down). Falls back to the static default when
            # no separate accelerator exists.
            # Pipeline.start() awaits autotune.prewarm() before spawning
            # workers, so this resolve hits the per-process cache when a
            # decoder is built on the event loop (the r5 advisor caught
            # the unwarmed probe stalling the apply loop for seconds).
            from . import autotune
            from .bitpack import layout_for_specs

            specs = self._host_specs()
            up = sum(w for _, _, w, _ in specs) + len(specs)
            down = layout_for_specs(specs).n_words * 4 if specs else 0
            self.device_min_rows = autotune.resolve_device_min_rows(
                len(self._dense), float(up + down), self.DEVICE_MIN_ROWS)

    # -- internals ----------------------------------------------------------

    def _widths(self, staged: StagedBatch) -> tuple[int, ...]:
        out = []
        for spec in self._dense:
            need = max(staged.max_field_len(spec.index),
                       _MIN_WIDTH.get(spec.kind, 4))
            out.append(bucket_width(need, hi=MAX_FIELD_WIDTH))
        return tuple(out)

    def _specs(self, staged: StagedBatch,
               widths: tuple[int, ...]) -> tuple:
        """(col_index, kind, gather_width, bit_width) per dense column.
        bit_width bounds the packed-output field sizes from the column's
        ACTUAL max text length (bucketed to even, clamped at the kind's
        layout-saturation width so jit signatures stay few) — tighter than
        the gather width, and every bit saved is fetch bandwidth on the
        device link."""
        from .bitpack import saturation_width

        out = []
        for spec, w in zip(self._dense, widths):
            bw = round_up_even(
                min(max(staged.max_field_len(spec.index), 1), w,
                    saturation_width(spec.kind)))
            out.append((spec.index, spec.kind, w, bw))
        return tuple(out)

    def _compute_host_specs(self) -> tuple:
        from .bitpack import saturation_width

        out = []
        for spec in self._dense:
            w = _HOST_WIDTH[spec.kind]
            bw = round_up_even(min(w, saturation_width(spec.kind)))
            out.append((spec.index, spec.kind, w, bw))
        return tuple(out)

    def _host_specs(self) -> tuple:
        """Data-independent specs for the host-CPU program (fixed gather
        widths per kind, bit widths at saturation): the signature never
        drifts with field lengths, so each (schema, row bucket) compiles
        exactly once. Computed at construction — see __init__."""
        return self._host_specs_cache

    def _can_nibble(self, widths: tuple[int, ...]) -> bool:
        return (all(s.kind in _NIBBLE_KINDS for s in self._dense)
                and all(w % 2 == 0 and w <= 255 for w in widths)
                and len(self._dense) > 0)

    def _pack_host(self, staged: StagedBatch, widths: tuple[int, ...],
                   allow_nibble: bool = True,
                   arena: "ArenaLease | None" = None,
                   row_capacity: int | None = None,
                   cols: "list[int] | None" = None,
                   phantom: tuple = ()):
        """Gather all dense fields into one byte matrix: nibble-packed C
        fast path (halves the upload) when the column mix allows, raw C
        pass otherwise, numpy as the last resort. Returns
        (bmat, lengths, nibble, bad_rows). The host-backend path packs raw
        (allow_nibble=False): there is no upload to halve, and skipping the
        nibble probe avoids a second compiled program per schema.

        `arena` supplies reusable preallocated buffers (ops/pipeline.py's
        pack stage); safe because every pack path overwrites all rows up
        to capacity. `row_capacity` > staged.row_capacity allocates mesh
        padding rows, zeroed after the pack (the C packers only write the
        staged capacity).

        `cols` is the staged column index feeding each byte-matrix slot
        (default: self._dense order — the exact layout). Canonical
        layouts pass their slot permutation plus `phantom` pad-slot
        indices: phantom slots pack a same-(kind,width) DONOR column
        through the C fast path (so the nibble alphabet scan sees only
        bytes a real slot already scanned) and are zeroed to all-NULL
        here, making padding invisible to the parsers and the fallback
        machinery."""
        from ..native import pack_bmat, pack_bmat_nibble

        cap = staged.row_capacity
        R = cap if row_capacity is None else row_capacity
        if cols is None:
            cols = [s.index for s in self._dense]

        def buf(shape, dtype):
            return arena.take(shape, dtype) if arena is not None \
                else np.empty(shape, dtype=dtype)

        def zero_tail(*arrays):
            if R > cap:
                for a in arrays:
                    a[cap:] = 0

        def zero_phantoms(bmat, lengths, nibble: bool):
            if not phantom:
                return
            w_off = 0
            offs = []
            for w in widths:
                offs.append(w_off)
                w_off += w
            for j in phantom:
                lengths[:, j] = 0
                o, w = offs[j], widths[j]
                if nibble:
                    bmat[:, o // 2 : (o + w) // 2] = 0
                else:
                    bmat[:, o : o + w] = 0

        total_w = sum(widths)
        ldtype = np.uint8 if max(widths, default=0) <= 255 else np.int32
        if allow_nibble and ldtype is np.uint8 and self._can_nibble(widths):
            bmat = buf((R, total_w // 2), np.uint8)
            lengths = buf((R, len(cols)), np.uint8)
            bad = buf((R,), np.uint8)
            if pack_bmat_nibble(
                    staged.data, np.ascontiguousarray(staged.offsets),
                    np.ascontiguousarray(staged.lengths),
                    cols, list(widths), bmat,
                    lengths, bad):
                zero_tail(bmat, lengths, bad)
                zero_phantoms(bmat, lengths, True)
                return bmat, lengths, True, bad
        bmat = buf((R, total_w), np.uint8)
        lengths = buf((R, len(cols)), ldtype)
        if ldtype is np.uint8 and pack_bmat(
                staged.data, np.ascontiguousarray(staged.offsets),
                np.ascontiguousarray(staged.lengths),
                cols, list(widths), bmat, lengths):
            zero_tail(bmat, lengths)
            zero_phantoms(bmat, lengths, False)
            return bmat, lengths, False, None
        bmat[:] = 0
        lengths[:] = 0
        data = staged.data
        n = len(data)
        phantom_set = frozenset(phantom)
        w_off = 0
        for j, (col, w) in enumerate(zip(cols, widths)):
            if j in phantom_set:
                w_off += w  # already zero (all-NULL padding slot)
                continue
            offs = staged.offsets[:, col].astype(np.int64)
            lens = np.minimum(staged.lengths[:, col], w)
            lengths[:cap, j] = lens
            idx = offs[:, None] + np.arange(w, dtype=np.int64)[None, :]
            np.clip(idx, 0, max(n - 1, 0), out=idx)
            if n:
                g = data[idx]
                mask = np.arange(w, dtype=np.int32)[None, :] < lens[:, None]
                bmat[:cap, w_off : w_off + w] = np.where(mask, g, 0)
            w_off += w
        return bmat, lengths, False, None

    def _device_filter_for(self, staged: StagedBatch):
        """The CompiledRowFilter to FUSE into this batch's device/host-XLA
        program, or None. Requires: a filter, a batch the caller allows
        filtering on (insert/COPY streams only — runtime/assembler clears
        the flag for runs carrying updates/deletes), and a predicate whose
        every referenced column is device-parsed with an exact int32
        comparison. Anything else falls back to `_host_filter_for`'s
        post-decode mask — correct, just without the fetch-bytes win."""
        rf = self._row_filter
        if rf is None or not staged.allow_row_filter \
                or not rf.device_supported:
            return None
        dense_idx = frozenset(s.index for s in self._dense)
        if not frozenset(rf.referenced_indices) <= dense_idx:
            return None
        return rf

    def _host_filter_for(self, staged: StagedBatch):
        """The filter to apply host-side AFTER an unfiltered decode (the
        oracle route, and device routes whose predicate is outside the
        device envelope)."""
        rf = self._row_filter
        if rf is None or not staged.allow_row_filter:
            return None
        return rf

    def _row_flags(self, staged: StagedBatch, specs: tuple,
                   pred, bad_rows, row_capacity: int) -> np.ndarray:
        """Per-row disposition vector for the fused filter program:
        0 dead (bucket/mesh padding), 1 live, 2 live + force-keep. Force-
        keep marks rows whose predicate-referenced device values cannot be
        trusted (COPY escapes, nibble-alphabet violations, oversized or
        TOASTed referenced fields): the device keeps them unconditionally
        and the host re-evaluates after oracle fixup, so the compacted
        output equals the host oracle bit for bit."""
        n = staged.n_rows
        flags = np.zeros(row_capacity, dtype=np.uint8)
        flags[:n] = 1
        force = np.zeros(n, dtype=bool)
        fb = staged.cpu_fallback_rows
        if len(fb):
            force[fb[fb < n]] = True
        if bad_rows is not None:
            force |= bad_rows[:n].astype(bool)
        ref = pred.referenced_indices
        widths = {i: w for i, _, w, _ in specs}
        for j in ref:
            if staged.max_field_len(j) > widths[j]:
                force |= staged.lengths[:n, j] > widths[j]
            toast_col = staged.toast[:n, j]
            if toast_col.any():
                force |= toast_col
        flags[:n][force] = 2
        return flags

    def _use_mesh(self, row_capacity: int) -> bool:
        # no divisibility requirement: the pack stage pads row capacity up
        # to a mesh.size multiple with all-NULL rows (staging.pad_to_
        # multiple), so odd buckets shard instead of silently falling back
        # to single-device dispatch
        return (self.mesh is not None
                and row_capacity >= self.mesh_min_rows)

    # -- pipeline stages (ops/pipeline.py runs pack on a worker thread,
    # -- dispatch immediately after; _device_call composes them for the
    # -- serial decode()/decode_async() path) -------------------------------

    def _pack_stage(self, staged: StagedBatch, specs: tuple,
                    host: bool = False,
                    arena: "ArenaLease | None" = None) -> "_PackedInputs":
        """Stage 1: host gather of all dense fields into (possibly pooled)
        staging buffers. Pure numpy/C — no jax calls, safe on any thread.
        Unfiltered batches pack into their CANONICAL layout (sorted
        slots + all-NULL phantom padding, program_store.canonical_plan)
        so the dispatch stage keys a shared program; the fused-filter
        path packs the exact layout (its predicate binds staged column
        indices)."""
        pred = self._device_filter_for(staged)
        plan = None
        cols = None
        phantom: tuple = ()
        if pred is None and specs:
            from . import program_store

            plan = program_store.canonical_plan(specs)
            widths = tuple(w for _, _, w, _ in plan.specs)
            if not plan.identity:
                cols = [self._dense[p].index for p in plan.pack_dense]
                phantom = plan.phantom_slots
        else:
            widths = tuple(w for _, _, w, _ in specs)
        use_mesh = not host and self._use_mesh(staged.row_capacity)
        cap = pad_to_multiple(staged.row_capacity, self.mesh.size) \
            if use_mesh else staged.row_capacity
        bmat, lengths, nibble, bad_rows = self._pack_host(
            staged, widths, allow_nibble=not host, arena=arena,
            row_capacity=cap, cols=cols, phantom=phantom)
        row_flags = None
        if pred is not None:
            row_flags = self._row_flags(staged, specs, pred, bad_rows, cap)
        return _PackedInputs(bmat, lengths, nibble, bad_rows, cap, use_mesh,
                             row_flags=row_flags, filtered=pred is not None,
                             plan=plan)

    @dispatch_stage
    @hot_loop
    def _dispatch_stage(self, staged: StagedBatch, specs: tuple,
                        packed: "_PackedInputs", host: bool = False):
        """Stage 2: start the device program on the packed inputs and
        return the in-flight device value. @dispatch_stage: the host-path
        `jax.device_put` is a committed UPLOAD riding the pipeline, not a
        sync point — fetches still belong at `_PendingDecode.result()`."""
        bmat, lengths = packed.bmat, packed.lengths
        # pspecs: what the PROGRAM is built from — the canonical layout
        # when the pack stage resolved one, the exact specs otherwise
        # (fused-filter dispatches). `specs` stays the exact per-real-
        # column view the completion path reasons about.
        pspecs = packed.plan.specs if packed.plan is not None else specs
        widths = tuple(w for _, _, w, _ in pspecs)
        if host:
            # committed CPU placement: jit compiles/executes this call on
            # the host CPU backend — same program, no accelerator round
            # trip (pallas is TPU-lowered, so host always takes the XLA
            # build; jit caches per input placement)
            dev = host_cpu_device()
            bmat = jax.device_put(bmat, dev)
            lengths = jax.device_put(lengths, dev)
        if self.use_pallas and not host:
            from .pallas_kernel import MAX_TOTAL_WIDTH, pallas_supported

            if not pallas_supported(pspecs):
                # wide schemas overflow the Mosaic compiler's appetite
                # for the unrolled parse chain (MAX_TOTAL_WIDTH) — take
                # the XLA program without a doomed compile attempt.
                # Flipping the FLAG (not silently routing)
                # keeps engine labels honest: callers (chip_smoke) read
                # which engine actually ran via use_pallas.
                import logging

                logging.getLogger("etl_tpu.ops").info(
                    "schema too wide for the pallas kernel "
                    "(total gather width %d > %d); using the XLA program",
                    sum(widths), MAX_TOTAL_WIDTH)
                self.use_pallas = False
        # the program cache is MODULE-level: decoders are created per
        # table and per copy partition, and identical (bucket, specs)
        # programs across instances must not recompile — the engine flag
        # rides in the key, so a pallas fallback just stops selecting
        # the pallas entries instead of clearing anything. The mesh slot
        # holds a canonical FINGERPRINT (axis names, shape, device ids —
        # parallel/mesh.mesh_cache_key), never the Mesh object: equal
        # meshes recreated across decoders share the program, while
        # decoders on different meshes (or mesh vs none) can never
        # collide on the same (specs, nibble) signature — the sharded
        # program returns (packed, shard_bad), a different output
        # STRUCTURE than the single-device array
        from ..parallel.mesh import mesh_cache_key

        pallas = self.use_pallas and not host
        pred = self._device_filter_for(staged) if packed.filtered else None
        pred_fp = pred.fingerprint() if pred is not None else None
        key = _host_fn_key(packed.row_capacity, specs, pred_fp) if host else \
            (packed.row_capacity, pspecs, packed.nibble,
             mesh_cache_key(self.mesh) if packed.use_mesh else None,
             pallas, pred_fp, False)
        if host:
            # observed-signature recording (ops/program_store.py): the
            # (canonical layout, row bucket) signatures a workload
            # ACTUALLY dispatched persist next to the executables, so a
            # restarted pipeline prewarms them — mega-seal buckets and
            # filtered programs the SchemaStore enumeration can't name.
            # Disarmed cost (no cache dir / already seen): one set probe.
            from . import program_store

            program_store.record_observed(key)
        row_flags = packed.row_flags
        if pred is not None and host:
            row_flags = jax.device_put(row_flags, dev)
        fn = _shared_fn_get(key)
        if fn is None:
            # miss: ops/program_store resolves it — disk load when a
            # cache dir is configured (warm restarts compile NOTHING),
            # else build + AOT compile + persist; the example args pin
            # the lowering to exactly what this call passes
            from . import program_store

            def _builder():
                return _build_device_fn(
                    pspecs, packed.nibble, pallas,
                    mesh=self.mesh if packed.use_mesh else None,
                    donate=not host and _donation_supported(), pred=pred)

            args = (bmat, lengths) if pred is None \
                else (bmat, lengths, row_flags)
            fn = program_store.acquire(key, _builder, args)
            _shared_fn_put(key, fn)
        elif self._telemetry:
            from ..telemetry.metrics import (ETL_COMPILE_CACHE_HITS_TOTAL,
                                             registry)

            registry.counter_inc(ETL_COMPILE_CACHE_HITS_TOTAL,
                                 labels={"layer": "memory"})
        self._fn_cache[key] = fn
        if packed.use_mesh and self._telemetry:
            from ..telemetry.metrics import (
                ETL_DECODE_MESH_BATCHES_TOTAL, ETL_DECODE_MESH_PAD_WASTE_RATIO,
                ETL_DECODE_MESH_PADDED_ROWS_TOTAL, ETL_DECODE_MESH_ROWS_TOTAL,
                ETL_DECODE_MESH_SHARDS, registry)

            registry.gauge_set(ETL_DECODE_MESH_SHARDS, self.mesh.size)
            registry.counter_inc(ETL_DECODE_MESH_BATCHES_TOTAL)
            registry.counter_inc(ETL_DECODE_MESH_ROWS_TOTAL,
                                 packed.row_capacity)
            # MESH padding only (cap − bucket capacity): bucket padding
            # below staged.row_capacity exists identically on the
            # single-device path and must not read as mesh waste
            pad = packed.row_capacity - staged.row_capacity
            if pad:
                registry.counter_inc(ETL_DECODE_MESH_PADDED_ROWS_TOTAL, pad)
            rows_total = registry.get_counter(ETL_DECODE_MESH_ROWS_TOTAL)
            pad_total = registry.get_counter(ETL_DECODE_MESH_PADDED_ROWS_TOTAL)
            registry.gauge_set(ETL_DECODE_MESH_PAD_WASTE_RATIO,
                               pad_total / rows_total if rows_total else 0.0)
        # a Mosaic rejection of the pallas program raises here like any
        # other compile error: a decoder that quietly took the XLA
        # program instead would report an engine it never ran
        if pred is not None:
            out = fn(bmat, lengths, row_flags)  # async dispatch
        else:
            out = fn(bmat, lengths)  # async dispatch
        if self.egress is not None and pred is None and specs:
            # stage 2b: the egress program renders wire text from the
            # decode output's device-resident words. Unfiltered batches
            # only (compacted words re-index rows) and never fatal — a
            # cold program, an un-renderable layout or any failure just
            # ships the batch without device egress.
            words = out[0] if isinstance(out, tuple) else out
            packed.egress = self._egress_stage(words, pspecs, packed, host)
        return out

    def _egress_stage(self, words, pspecs: tuple,
                      packed: "_PackedInputs", host: bool):
        from . import egress as egress_mod

        try:
            from . import program_store

            plan = egress_mod.plan_for_specs(pspecs, self.egress)
            if plan is None:
                return None
            from ..parallel.mesh import mesh_cache_key

            mesh = self.mesh if packed.use_mesh else None
            key = egress_mod.egress_fn_key(
                packed.row_capacity, pspecs, self.egress,
                mesh_cache_key(mesh) if mesh is not None else None, host)

            def _builder():
                return egress_mod.build_egress_fn(pspecs, plan, mesh=mesh)

            fn = egress_mod.egress_fn_ready(
                key, _builder, (words,),
                blocking=not self.nonblocking_compile)
            if fn is None:
                return None
            self._fn_cache[key] = fn
            if host:
                # observed-signature recording, same as decode host
                # dispatches: a restarted pipeline prewarms the egress
                # programs the workload actually used
                program_store.record_observed(key)
            ebytes, elens = fn(words)  # async dispatch
            if self._telemetry:
                from ..telemetry.metrics import (
                    ETL_EGRESS_DEVICE_BATCHES_TOTAL, registry)

                registry.counter_inc(ETL_EGRESS_DEVICE_BATCHES_TOTAL)
            return (ebytes, elens, plan)
        except Exception:
            import logging

            egress_mod.count_failure()
            logging.getLogger("etl_tpu.ops").warning(
                "device egress dispatch failed; batch ships without "
                "wire buffers", exc_info=True)
            return None

    def _device_call(self, staged: StagedBatch, specs: tuple,
                     host: bool = False):
        packed = self._pack_stage(staged, specs, host)
        return self._dispatch_stage(staged, specs, packed, host), packed

    def _gather_string_arrow(self, staged: StagedBatch, spec: _ColSpec,
                             valid: np.ndarray):
        """Vectorized scatter-gather of a string column into an Arrow array:
        no per-row Python objects — the columnar-native fast path."""
        import pyarrow as pa

        from ..native import gather_string

        n = staged.n_rows
        lens = np.where(valid[:n], staged.lengths[:n, spec.index], 0)
        total = int(lens.sum())
        if total == 0:  # all-null/empty: both buffers must still be defined
            return pa.StringArray.from_buffers(
                n, pa.py_buffer(np.zeros(n + 1, dtype=np.int32)),
                pa.py_buffer(np.zeros(0, dtype=np.uint8)),
                pa.array(valid[:n]).buffers()[1] if n else None)
        arrow_offsets = np.empty(n + 1, dtype=np.int32)
        values = np.empty(total, dtype=np.uint8)
        wrote = gather_string(
            staged.data, np.ascontiguousarray(staged.offsets[:n]),
            np.ascontiguousarray(staged.lengths[:n]),
            np.ascontiguousarray(valid[:n], dtype=np.uint8), spec.index,
            arrow_offsets, values)
        if wrote != total:
            # numpy fallback (no native lib)
            offs = staged.offsets[:n, spec.index].astype(np.int32)
            lens32 = lens.astype(np.int32)
            arrow_offsets[0] = 0
            np.cumsum(lens32, out=arrow_offsets[1:])
            if total:
                starts_rep = np.repeat(offs, lens32)
                prefix_rep = np.repeat(arrow_offsets[:-1], lens32)
                idx = np.arange(total, dtype=np.int32)
                idx -= prefix_rep
                idx += starts_rep
                values = staged.data[idx]
        validity = pa.array(valid[:n]).buffers()[1]
        # py_buffer over the ndarrays directly — no tobytes() copies
        return pa.StringArray.from_buffers(
            n, pa.py_buffer(arrow_offsets), pa.py_buffer(values), validity)

    # object kinds whose Postgres text IS the exact destination form
    # (Arrow/numeric-as-text stance, models/table_row.to_arrow): keep them
    # as Arrow text columns, parse to Python objects only on value() access
    _LAZY_TEXT_KINDS = frozenset({
        CellKind.STRING, CellKind.NUMERIC, CellKind.UUID, CellKind.JSON,
        CellKind.TIMETZ, CellKind.INTERVAL,
    })

    def _decode_object_column(self, staged: StagedBatch, spec: _ColSpec,
                              valid: np.ndarray) -> Any:
        col = self.schema.replicated_columns[spec.index]
        n = staged.n_rows
        if spec.kind in self._LAZY_TEXT_KINDS:
            # safe on the COPY path too: stage_copy_chunk routes every row
            # containing a backslash beyond bare-\N nulls to
            # cpu_fallback_rows, and the caller masks those out of `valid`
            # — the remaining rows' raw bytes ARE the exact text (the
            # per-row Python loop here measured 10× the whole decode)
            return self._gather_string_arrow(staged, spec, valid)
        # STRING never reaches here: it is in _LAZY_TEXT_KINDS, so the
        # Arrow-gather path above always returns first
        out: list[Any] = [None] * n
        offs = staged.offsets[:, spec.index]
        lens = staged.lengths[:, spec.index]
        data = staged.data
        oid = col.type_oid
        for i in np.flatnonzero(valid[:n]):
            text = data[offs[i] : offs[i] + lens[i]].tobytes().decode("utf-8")
            out[i] = parse_cell_text(text, oid)
        return out

    def _cpu_fixup(self, staged: StagedBatch, rows: np.ndarray,
                   columns: list[Column]) -> None:
        """Re-decode flagged rows with the CPU oracle and patch columns."""
        from ..models.table_row import _to_dense  # late: avoid cycle
        from ..postgres.codec.copy_text import unescape_copy_field

        cols = self.schema.replicated_columns
        for c in columns:
            if c.is_arrow and rows.size:
                # rare: fixup needs mutability — densify, PARSING lazy text
                # so the column's value type stays consistent across rows
                if c.lazy_text_oid is not None:
                    oid = c.lazy_text_oid
                    c.data = [None if v is None else parse_cell_text(v, oid)
                              for v in c.data.to_pylist()]
                    c.lazy_text_oid = None
                else:
                    c.data = c.data.to_pylist()
        for i in rows:
            for j, col in enumerate(cols):
                c = columns[j]
                raw = staged.field_bytes(int(i), j)
                if raw is None:
                    continue
                if staged.copy_escapes:
                    raw = unescape_copy_field(raw)
                value = parse_cell_text(raw.decode("utf-8"), col.type_oid)
                if c.is_dense:
                    try:
                        c.data[i] = _to_dense(c.schema.kind, value) \
                            if value is not None else 0
                    except (OverflowError, ValueError) as e:
                        # value doesn't fit the column's declared type —
                        # corrupt data, same as a Rust i32 parse failure
                        from ..models.errors import ErrorKind, EtlError

                        raise EtlError(
                            ErrorKind.ROW_CONVERSION_FAILED,
                            f"row {i} col {col.name}: value out of range "
                            f"for {col.type_name}: {value!r}") from e
                else:
                    c.data[i] = value
                c.validity[i] = value is not None

    def _assemble(self, staged: StagedBatch, specs: tuple, packed_np,
                  bad_rows=None,
                  plan=None) -> "tuple[ColumnarBatch, np.ndarray]":
        """Shared completion core: fetched packed words (+ the staged
        bookkeeping they index) → typed columns + CPU fixup. For a fused-
        filter decode `staged` is the COMPACTED view (staging.gather_rows)
        and `packed_np` the count-sized slice, so every index here —
        including the fallback rows returned for the caller's post-fixup
        predicate re-check — lives in the compacted space. With `plan`
        (the canonical layout the batch packed into) the words carry
        canonical slot order: each real column unpacks from
        plan.slot_of[j] and the phantom padding slots are never read —
        column outputs index by schema position, so the decoded batch is
        byte-identical to the exact layout's."""
        from .bitpack import layout_for_specs, unpack_host

        n = staged.n_rows
        cols = self.schema.replicated_columns
        valid_full = ~staged.nulls & ~staged.toast

        columns: list[Column] = [None] * len(cols)  # type: ignore[list-item]
        fallback = set(int(r) for r in staged.cpu_fallback_rows)
        if packed_np is None and self._dense:
            # small batch: every row goes to the oracle once; skip the
            # per-column width/ok machinery entirely
            fallback.update(range(n))
        if bad_rows is not None:
            # nibble pack flagged bytes outside the symbol alphabet
            fallback.update(np.flatnonzero(bad_rows[:n]).tolist())
        if packed_np is not None:
            for spec, (_, _, w, _) in zip(self._dense, specs):
                if staged.max_field_len(spec.index) > w:
                    too_big = staged.lengths[:n, spec.index] > w
                    fallback.update(np.flatnonzero(too_big).tolist())

        pspecs = plan.specs if plan is not None else specs
        layout = layout_for_specs(pspecs) if packed_np is not None else None
        for j, spec in enumerate(self._dense):
            valid = valid_full[:n, spec.index].copy()
            toast_col = staged.toast[:n, spec.index]
            if packed_np is None:
                # small batch: host decode of every row via the oracle
                data = np.zeros(n, dtype=dense_dtype(spec.kind))
            else:
                slot = plan.slot_of[j] if plan is not None else j
                ok, comps = unpack_host(layout, packed_np, slot, n)
                bad = ~ok & valid
                if bad.any():
                    fallback.update(np.flatnonzero(bad).tolist())
                data = _combine(spec.kind, comps)
            columns[spec.index] = Column(
                cols[spec.index], data, valid,
                toast_col if toast_col.any() else None)

        for spec in self._object:
            valid = valid_full[:, spec.index]
            toast_col = staged.toast[:n, spec.index]
            data_list = self._decode_object_column(
                staged, spec,
                valid & ~np.isin(np.arange(staged.row_capacity),
                                 list(fallback)) if fallback else valid)
            lazy_oid = None
            if spec.kind in self._LAZY_TEXT_KINDS \
                    and spec.kind is not CellKind.STRING:
                lazy_oid = cols[spec.index].type_oid
            columns[spec.index] = Column(
                cols[spec.index], data_list, valid[:n].copy(),
                toast_col if toast_col.any() else None,
                lazy_text_oid=lazy_oid)

        from ..telemetry.metrics import (
            ETL_DEVICE_DECODE_FALLBACK_ROWS_TOTAL, registry)

        rows_arr = np.zeros(0, dtype=np.int64)
        if fallback:
            rows_arr = np.asarray(sorted(r for r in fallback if r < n),
                                  dtype=np.int64)
            self._cpu_fixup(staged, rows_arr, columns)
            if self._telemetry:
                registry.counter_inc(ETL_DEVICE_DECODE_FALLBACK_ROWS_TOTAL,
                                     len(rows_arr))
        return ColumnarBatch(self.schema, columns), rows_arr

    def _shard_health(self, shard_bad) -> None:
        from ..telemetry.metrics import (
            ETL_DECODE_MESH_FALLBACK_CANDIDATE_ROWS_TOTAL,
            ETL_DECODE_MESH_SHARD_FALLBACK_CANDIDATES, registry)

        sb = np.asarray(shard_bad)
        total_bad = float(sb.sum())
        if total_bad:
            registry.counter_inc(
                ETL_DECODE_MESH_FALLBACK_CANDIDATE_ROWS_TOTAL, total_bad)
        # last-batch shard-health snapshot: a single sick shard (one
        # device corrupting its block) shows up here as skew
        for s in range(sb.shape[0]):
            registry.gauge_set(ETL_DECODE_MESH_SHARD_FALLBACK_CANDIDATES,
                               float(sb[s]), {"shard": str(s)})

    def _filter_telemetry(self, n_in: int, n_out: int,
                          fetched_bytes: float) -> None:
        from ..telemetry.metrics import (ETL_DECODE_FETCHED_BYTES_TOTAL,
                                         ETL_DECODE_FILTER_SELECTIVITY,
                                         ETL_DECODE_ROWS_FILTERED_TOTAL,
                                         registry)

        if not self._telemetry:
            return
        if fetched_bytes:
            registry.counter_inc(ETL_DECODE_FETCHED_BYTES_TOTAL,
                                 float(fetched_bytes))
        if n_in > n_out:
            registry.counter_inc(ETL_DECODE_ROWS_FILTERED_TOTAL,
                                 n_in - n_out)
        if n_in and self._row_filter is not None:
            registry.gauge_set(ETL_DECODE_FILTER_SELECTIVITY, n_out / n_in)

    def _complete(self, staged: StagedBatch, specs: tuple,
                  packed, bad_rows=None,
                  meta: "_PackedInputs | None" = None) -> ColumnarBatch:
        from ..telemetry import spans
        from ..telemetry.metrics import (ETL_DECODE_EGRESS_FETCH_SECONDS,
                                         ETL_DECODE_RESULT_WAIT_SECONDS,
                                         ETL_DECODE_UNPACK_SECONDS,
                                         ETL_DEVICE_DECODE_ROWS_TOTAL,
                                         registry)

        n = staged.n_rows
        ids = {"batch_id": staged.batch_id, "rows": n}
        if self._telemetry:
            # n = staged.n_rows: bucket- and mesh-padding tail rows are
            # excluded from every error/telemetry counter by construction
            registry.counter_inc(ETL_DEVICE_DECODE_ROWS_TOTAL, n)
        if meta is not None and meta.filtered and packed is not None:
            batch = self._complete_filtered(staged, specs, packed,
                                            bad_rows, meta)
        else:
            shard_bad = None
            if isinstance(packed, tuple):
                # mesh-sharded dispatch: (packed words, per-shard fallback-
                # candidate counts reduced on device). The counts are HOST-
                # aggregated into shard-health telemetry; the exact
                # fallback set still comes from the unpacked ok bits, so
                # sharded and single-device decodes stay byte-identical.
                packed, shard_bad = packed
            packed_np = None
            if packed is not None:
                # the fetch stage's first part: the device (or host-XLA)
                # program finishing and its packed words landing
                with spans.span("decode.result_wait",
                                ETL_DECODE_RESULT_WAIT_SECONDS, **ids):
                    packed_np = np.asarray(packed)
            if shard_bad is not None and self._telemetry:
                self._shard_health(shard_bad)
            with spans.span("decode.unpack", ETL_DECODE_UNPACK_SECONDS,
                            **ids):
                batch, fixups = self._assemble(
                    staged, specs, packed_np, bad_rows,
                    plan=meta.plan if meta is not None else None)
            fetched = packed_np.nbytes if packed_np is not None else 0.0
            host_rf = self._host_filter_for(staged)
            if meta is not None and meta.egress is not None \
                    and host_rf is None:
                # attach the device-rendered wire buffers; `fixups` (the
                # oracle-patched rows) become the untrusted set whose
                # lines destinations re-render per value. Host-filtered
                # batches skip the attach: take() re-indexes rows.
                from . import egress as egress_mod

                try:
                    with spans.span("decode.egress_fetch",
                                    ETL_DECODE_EGRESS_FETCH_SECONDS, **ids):
                        batch.device_egress = egress_mod.materialize(
                            meta.egress, meta.plan, self._dense, n, fixups)
                except Exception:
                    import logging

                    egress_mod.count_failure()
                    logging.getLogger("etl_tpu.ops").warning(
                        "egress materialization failed; batch ships "
                        "without wire buffers", exc_info=True)
            if host_rf is not None:
                # predicate outside the device envelope (or an oracle-
                # routed batch): the same filter applies host-side over
                # the decoded batch — correct, without the fetch win
                keep = host_rf.host_keep(batch)
                surv = np.flatnonzero(keep).astype(np.int64)
                batch = batch.take(surv)
                batch.source_rows = surv
                self._filter_telemetry(n, len(surv), fetched)
            elif self._telemetry and fetched:
                from ..telemetry.metrics import \
                    ETL_DECODE_FETCHED_BYTES_TOTAL

                registry.counter_inc(ETL_DECODE_FETCHED_BYTES_TOTAL,
                                     float(fetched))
        return batch

    def _complete_filtered(self, staged: StagedBatch, specs: tuple,
                           packed, bad_rows,
                           meta: "_PackedInputs") -> ColumnarBatch:
        """Completion of a fused coerce→filter→pack dispatch: fetch the
        survivor count + the 1-bit-per-row keep mask, fetch a count-sized
        slice of the compacted words (single device — fetched bytes scale
        with selectivity; the mesh path fetches its row-sharded words
        whole and slices per shard block on host), then run the normal
        completion against the COMPACTED staged view. Fallback
        bookkeeping lives in the compacted index space throughout;
        force-kept and fixed-up survivors get one exact host
        re-evaluation so the final batch is byte-identical to the host
        oracle."""
        from ..telemetry import spans
        from ..telemetry.metrics import (ETL_DECODE_RESULT_WAIT_SECONDS,
                                         ETL_DECODE_UNPACK_SECONDS)

        ids = {"batch_id": staged.batch_id, "rows": staged.n_rows}
        with spans.span("decode.result_wait", ETL_DECODE_RESULT_WAIT_SECONDS,
                        **ids):
            survivors, words_np, fetched = self._fetch_filtered(packed,
                                                                meta)
        with spans.span("decode.unpack", ETL_DECODE_UNPACK_SECONDS, **ids):
            return self._assemble_filtered(staged, specs, bad_rows, meta,
                                           survivors, words_np, fetched)

    def _fetch_filtered(self, packed, meta: "_PackedInputs") -> tuple:
        """The fetches of a fused-filter dispatch: (survivor row indices,
        their compacted words, bytes fetched)."""
        from .bitpack import unpack_keep_mask
        from .staging import slice_rows

        mesh_shards = self.mesh.size if meta.use_mesh else None
        if mesh_shards is not None:
            words_d, mask_d, counts_d, shard_bad_d = packed
            if self._telemetry:
                self._shard_health(shard_bad_d)
        else:
            words_d, mask_d, counts_d = packed
        counts = np.asarray(counts_d)
        mask_np = np.asarray(mask_d)
        R = meta.row_capacity
        fetched = float(counts.nbytes + mask_np.nbytes)
        survivors = unpack_keep_mask(mask_np, R)
        if mesh_shards is None:
            S = int(counts[0])
            Sb = slice_rows(S, R)
            if Sb:
                # count-sized device slice: the only words bytes that
                # ever cross the link are the survivors' (+ the slice
                # bucket's pad slack)
                words_np = np.asarray(words_d[:, :Sb])
                fetched += words_np.nbytes
                words_np = words_np[:, :S]
            else:
                words_np = np.zeros((words_d.shape[0], 0), dtype=np.uint32)
        else:
            words_full = np.asarray(words_d)
            fetched += words_full.nbytes
            rps = R // mesh_shards
            parts = [np.arange(s * rps, s * rps + int(counts[s]),
                               dtype=np.int64)
                     for s in range(mesh_shards) if counts[s] > 0]
            sel = np.concatenate(parts) if parts \
                else np.zeros(0, dtype=np.int64)
            words_np = words_full[:, sel]
            S = len(sel)
        assert len(survivors) == S, (len(survivors), S)
        return survivors, words_np, fetched

    def _assemble_filtered(self, staged: StagedBatch, specs: tuple,
                           bad_rows, meta: "_PackedInputs", survivors,
                           words_np, fetched: float) -> ColumnarBatch:
        pred = self._device_filter_for(staged)
        S = len(survivors)
        cstaged = staged.gather_rows(survivors)
        cbad = bad_rows[survivors] if bad_rows is not None else None
        batch, fixup_rows = self._assemble(cstaged, specs, words_np, cbad)
        # exact arbitration for rows the device could not judge: force-
        # kept rows (escapes / nibble / oversize / TOAST on a referenced
        # field) and every fixed-up row re-evaluate on their DECODED
        # values; rows the re-check rejects compact out host-side
        suspect = np.zeros(S, dtype=bool)
        if meta.row_flags is not None and S:
            suspect |= meta.row_flags[survivors] > 1
        if len(fixup_rows):
            suspect[fixup_rows] = True
        if suspect.any():
            keep_h = pred.host_keep(batch)
            final = ~suspect | keep_h
            if not final.all():
                sel2 = np.flatnonzero(final).astype(np.int64)
                batch = batch.take(sel2)
                survivors = survivors[sel2]
        batch.source_rows = survivors
        self._filter_telemetry(staged.n_rows, len(survivors), fetched)
        return batch

    # -- public -------------------------------------------------------------

    def _route(self, staged: StagedBatch) -> tuple[str, tuple]:
        """Pick the decode path for this batch: ("device"|"host"|"oracle",
        specs). Owns the routed-rows telemetry so the pipelined and serial
        entry points count identically."""
        cols = self.schema.replicated_columns
        if len(cols) != staged.n_cols:
            raise ValueError(
                f"staged batch has {staged.n_cols} cols, schema expects "
                f"{len(cols)}")
        from ..telemetry.metrics import (
            ETL_DECODE_DEVICE_PARSED_CELLS_TOTAL,
            ETL_DECODE_ROUTED_DEVICE_ROWS_TOTAL,
            ETL_DECODE_ROUTED_HOST_ROWS_TOTAL,
            ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL, registry)

        if host_oracle_forced():
            # supervision escalation (supervisor._detected): repeated
            # device-side stalls park EVERY batch on the host oracle
            # until the degrade cooldown lapses — availability beats the
            # device-decode win, same stance as the per-batch OOM
            # fallback in ops/pipeline._process
            if self._telemetry:
                registry.counter_inc(ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL,
                                     staged.n_rows)
            return "oracle", ()
        if self._dense and staged.n_rows >= self.device_min_rows:
            if self._telemetry:
                registry.counter_inc(ETL_DECODE_ROUTED_DEVICE_ROWS_TOTAL,
                                     staged.n_rows)
                registry.counter_inc(
                    ETL_DECODE_DEVICE_PARSED_CELLS_TOTAL,
                    staged.n_rows * self.n_device_kind_columns)
            return "device", self._specs(staged, self._widths(staged))
        if self._dense and staged.n_rows >= self.host_min_rows:
            specs = self._host_specs()
            if self.nonblocking_compile \
                    and not _host_fn_ready(self, staged, specs):
                # cold program: decode THIS batch on the oracle while the
                # build runs on a background thread — a synchronous
                # first-touch compile here (tens of seconds on wide
                # schemas) would freeze apply progress past the stall
                # deadline and spiral the watchdog into restarts
                if self._telemetry:
                    registry.counter_inc(ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL,
                                         staged.n_rows)
                return "oracle", ()
            if self._telemetry:
                registry.counter_inc(ETL_DECODE_ROUTED_HOST_ROWS_TOTAL,
                                     staged.n_rows)
            return "host", specs
        if self._telemetry:
            registry.counter_inc(ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL,
                                 staged.n_rows)
        return "oracle", ()

    @hot_loop
    def decode_async(self, staged: StagedBatch) -> _PendingDecode:
        """Dispatch the device work and return immediately; stage the next
        batch while this one is in flight. @hot_loop: dispatch-only — the
        fetch happens at `_PendingDecode.result()` on the consumer.
        (ops/pipeline.DecodePipeline runs the same route→pack→dispatch
        chain with the pack stage on a worker thread and pooled arenas.)"""
        mode, specs = self._route(staged)
        if mode == "oracle":
            return _PendingDecode(self, staged, (), None, None)
        value, packed = self._device_call(staged, specs,
                                          host=mode == "host")
        return _PendingDecode(self, staged, specs, value, packed)

    def decode(self, staged: StagedBatch) -> ColumnarBatch:
        return self.decode_async(staged).result()
