"""The program's own span recorder.

One fixed-size in-memory ring of `(name, t0_ns, t1_ns, thread, id,
parent_id)` records on `time.perf_counter_ns()` — the clock the
benchmark harness and the device trace's marker already share — beside
the always-on `registry` histograms. No exporter, no configuration, no
switch: the recorder is always on, and granularity is the cost control
(a span per drain, seal, decode batch, flush, copy chunk, HTTP request or
periodic tick — never per frame, row or socket read).

  - `span(name, series=None, **ids)`: context manager for work that
    starts and ends in one call on one thread. It also enters a
    `jax.profiler.TraceAnnotation(name, **ids)` for the same interval, so
    inside any profiler session the span sits in the trace's host plane
    beside the device operations; outside a session that is the
    TraceMe's own `is_enabled()` check and no object. JAX is never imported from here: a process
    that has not loaded it has no profiler session to annotate.
  - `record(name, t0_ns, t1_ns, series=None, **ids)`: intervals whose
    ends lie in different callbacks (queue waits). Ring and histogram
    only.
  - `fold()`: a `series` observation is not made on the spot: the
    duration waits in a list and reaches its histogram before the
    registry is next read (`registry.before_read`), on the memory
    monitor's tick, or when 8,192 have piled up — so `/metrics` and
    the window deltas the benchmark takes are exact, and the span on the
    replication-lag path pays an append instead of a lock.
  - `snapshot(lo_ns, hi_ns)`: the ring's records overlapping [lo, hi) as
    arrays in start order; `names()`.

Identifiers: `batch_id=` (a sealed run / copy chunk, minted by
`next_batch_id()`) or `flush_id=` (`next_flush_id()`) is the record's
`id`; `parent=` its parent. Any other keyword goes to the trace
annotation only.

Appends take no lock: the slot index comes from `itertools.count`
(atomic under the interpreter lock) and a list-slot store of one tuple is
atomic, so the loop thread, the decode workers and `asyncio.to_thread`
callers share one ring and a reader never sees a torn record.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time

import numpy as np

from .metrics import registry

#: ring slots. At ~220 B a record (tuple + two clock ints + ids) that is
#: ~14 MB when full; at the null drain's ~3,000 spans/s it holds > 20 s.
CAPACITY = 1 << 16

_clock = time.perf_counter_ns
_ring: list = [None] * CAPACITY
_seq = itertools.count()
_batch_ids = itertools.count(1)
_flush_ids = itertools.count(1)
_local = threading.local()
_threads: list = []  # thread idents, by the small index records carry
_threads_lock = threading.Lock()
_annotation = None  # jax.profiler.TraceAnnotation once JAX is loaded
_pending: list = []  # (series, duration_ns) not yet in the registry
_PENDING_MAX = 8192
_fold_lock = threading.Lock()


def next_batch_id() -> int:
    return next(_batch_ids)


def next_flush_id() -> int:
    return next(_flush_ids)


def _thread_index() -> int:
    """This thread's index in `_threads`, assigned on its first record."""
    ident = threading.get_ident()
    with _threads_lock:
        # the OS reuses idents of threads that ended (one decode worker
        # per copy partition): so does the table
        if ident not in _threads:
            _threads.append(ident)
        _local.index = _threads.index(ident)
    return _local.index


def _resolve_annotation():
    global _annotation
    if "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class _Span:
    __slots__ = ("name", "series", "ids", "t0", "_ann", "_dropped")

    def __init__(self, name: str, series, ids: dict):
        self.name = name
        self.series = series
        self.ids = ids
        self._dropped = False

    def __enter__(self) -> "_Span":
        make = _annotation or _resolve_annotation()
        # the annotation object only while a profiler session is live:
        # the check is the TraceMe's own, a tenth of a microsecond
        if make is not None and make.is_enabled():
            self._ann = make(self.name, **self.ids)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0 = _clock()
        return self

    def drop(self) -> None:
        """Leave nothing in the ring or the histogram (a drain that found
        the buffer empty)."""
        self._dropped = True

    def __exit__(self, *exc) -> None:
        t1 = _clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if not self._dropped:
            record(self.name, self.t0, t1, self.series, **self.ids)


def span(name: str, series: "str | None" = None, **ids) -> _Span:
    return _Span(name, series, ids)


def record(name: str, t0_ns: int, t1_ns: int, series: "str | None" = None,
           **ids) -> None:
    try:
        index = _local.index
    except AttributeError:
        index = _thread_index()
    _ring[next(_seq) % CAPACITY] = (
        name, t0_ns, t1_ns, index,
        (ids.get("flush_id") or ids.get("batch_id") or 0) if ids else 0,
        ids.get("parent", 0) if ids else 0)
    if series is not None:
        # deferred: an observation under the registry's lock costs several
        # times the ring append when the caches are cold (a transaction
        # after an idle gap), and these sit on the replication-lag path
        _pending.append((series, t1_ns - t0_ns))
        if len(_pending) >= _PENDING_MAX:
            fold()


def fold() -> None:
    """Observe the deferred durations in their histograms. Runs before
    every histogram read (`registry.before_read`), on the memory monitor's
    tick, and inline when `_PENDING_MAX` have piled up."""
    with _fold_lock:
        n = len(_pending)
        if n:
            batch = _pending[:n]
            del _pending[:n]  # appends meanwhile land beyond n: kept
            registry.histogram_observe_many(
                (series, ns * 1e-9) for series, ns in batch)


registry.before_read(fold)


def now_ns() -> int:
    return _clock()


def snapshot(lo_ns: "int | None" = None, hi_ns: "int | None" = None) -> dict:
    """Records overlapping [lo_ns, hi_ns) (either bound may be None), in
    start order: {"name": object[n], "t0_ns", "t1_ns": int64[n], "thread":
    int64[n] (thread idents), "id", "parent": int64[n]}."""
    recs = [r for r in list(_ring) if r is not None
            and (hi_ns is None or r[1] < hi_ns)
            and (lo_ns is None or r[2] > lo_ns)]
    recs.sort(key=lambda r: r[1])
    idents = list(_threads)
    cols = list(zip(*recs)) if recs else [()] * 6
    out = {"name": np.array(cols[0], dtype=object)}
    for key, col in zip(("t0_ns", "t1_ns", "thread", "id", "parent"),
                        cols[1:]):
        out[key] = np.array(col, dtype=np.int64)
    out["thread"] = np.array([idents[i] for i in cols[3]], dtype=np.int64)
    return out


def names() -> list:
    return sorted({r[0] for r in list(_ring) if r is not None})
