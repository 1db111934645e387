"""Canonical-layout program store: every compiled decode program, one
subsystem — layout canonicalization, AOT disk persistence, and startup
prewarm.

Three layers, one key space (ops/engine._SHARED_FN_CACHE keys):

1. **Canonicalization** (`canonical_plan`). The decode program traced by
   `bitpack.parse_and_pack` is a pure function of the *sequence* of
   `(kind, gather_width, bit_width)` triples — the `col_index` slot in
   engine specs only selects which staged column feeds each byte-matrix
   slot, host-side, at pack time (the fused row-filter path is the one
   exception and is excluded below). So N tables whose column vectors
   are the same multiset compile ONE program instead of N:

     - *index erasure*: program specs carry positional indices, never
       staged column positions — two single-int4 tables share whatever
       columns sit around that int4;
     - *sort*: dense columns are packed in (kind, width, bit-width)
       order, so column ORDER stops mattering (DDL churn that drops and
       re-adds a column lands back on the same program);
     - *count padding*: each (kind, width, bit-width) group's column
       count rounds up to a small bucket ladder (≤1.5× steps), with the
       padded "phantom" slots packed as all-NULL columns — adding one
       column to a 5-int table stays inside the 6-slot program.

   The pack stage gathers real columns into their canonical slots and
   zeroes the phantom slots (zero length = NULL to the parsers, never a
   fallback candidate), and completion unpacks each real column from its
   canonical slot — the decoded ColumnarBatch is byte-identical to the
   exact layout's because column outputs are indexed by schema position,
   not slot position (proved the same way Pallas==XLA is:
   tests/test_program_store.py byte-identity matrix). Fused-row-filter
   programs skip canonicalization: the predicate evaluator is bound to
   staged column indices and is per-table anyway (its fingerprint is in
   the key).

2. **Disk persistence** (`acquire`/`try_load`/`save`). With a cache dir
   configured (`BatchConfig.program_cache_dir` or
   $ETL_TPU_PROGRAM_CACHE_DIR), cache misses AOT-compile
   (`jit(...).lower(args).compile()`) and serialize the executable
   (jax.experimental.serialize_executable) to
   `<dir>/<version-tag>/<fingerprint>.prog`; a restarted process loads
   the executable instead of re-paying the XLA build (measured: a ~32 s
   120-column build loads back in well under a second). The version tag
   hashes jaxlib/jax versions, the backend, the decode-source hash, and
   the host CPU feature flags — an XLA:CPU AOT result carries the
   compile machine's feature set and its loader warns of SIGILL on a
   host that lacks one, which can only happen by byte-sharing a dir
   across heterogeneous machines, and the tag keeps those populations
   in separate subdirectories. Writes are atomic
   (tmp + rename), so concurrent processes can share a dir; a corrupted
   or stale file is deleted and treated as a miss — degrade is always a
   clean rebuild, never a crash.

3. **Prewarm** (`warm_host_programs` / Pipeline.start). At startup the
   pipeline enumerates the SchemaStore's table schemas, resolves their
   canonical layouts, and warms the deduped host-program keys through
   the SAME `engine._host_fn_ready` machinery the nonblocking streaming
   decoders use: disk hits load synchronously (a warm restart reaches
   its first durable batch with ZERO fresh XLA builds — held by
   tests/test_program_store.py::TestPersistence via the compile
   counter), cold keys
   compile on background threads while batches decode on the host
   oracle. One API, three callers: pipeline prewarm, the streaming
   decoders' nonblocking first touch, and the chaos restart scenarios.
"""

from __future__ import annotations

import dataclasses
import enum
import hashlib
import logging
import os
import pickle
import threading
import time

log = logging.getLogger("etl_tpu.ops.program_store")

# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------

#: module switch for tests / emergency opt-out ($ETL_TPU_CANONICAL_LAYOUTS=0)
CANONICALIZE = os.environ.get("ETL_TPU_CANONICAL_LAYOUTS", "1") != "0"

#: per-(kind, width, bit-width) column-count ladder: ≤1.5× steps bound the
#: phantom-slot waste at 50% of a group's columns (host programs don't
#: care; on the device path upload bytes are the binding resource, and
#: the same ladder keeps the trade explicit)
_COUNT_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192,
                  256)

#: the C packers index at most 256 slots per row; a canonical layout that
#: would pad past this falls back to sort + index erasure only
MAX_SLOTS = 256


def pad_count(n: int) -> int:
    for b in _COUNT_BUCKETS:
        if n <= b:
            return b
    return n


@dataclasses.dataclass(frozen=True)
class CanonicalPlan:
    """How one exact spec tuple maps onto its canonical program layout.

    specs:      canonical program specs (positional col indices) — what
                the jit key and `build_device_program` see
    slot_of:    dense position j (engine `_dense` order) → canonical slot
    pack_dense: per canonical slot, the dense position whose staged
                column feeds it; phantom slots name their group's first
                real member as a pack DONOR (same kind and width, so the
                nibble packer's alphabet scan sees a byte subset of what
                the real slot already scanned) and are zeroed after the
                pack
    phantom_slots: slots that are padding (zero-length ⇒ all-NULL)
    identity:   True when slots == dense positions and nothing is padded
                (the pack path then skips the permutation machinery;
                index erasure in `specs` still applies)
    """

    specs: tuple
    slot_of: tuple
    pack_dense: tuple
    phantom_slots: tuple
    identity: bool

    @property
    def n_slots(self) -> int:
        return len(self.specs)


_PLAN_CACHE: dict = {}
_PLAN_LOCK = threading.Lock()
#: distinct canonical layouts (spec tuples) seen this process — the
#: etl_decode_canonical_layouts gauge; its size vs tables-seen is the
#: sharing ratio canonicalization buys
_LAYOUTS_SEEN: set = set()


def _identity_plan(specs: tuple) -> CanonicalPlan:
    n = len(specs)
    pos = tuple(range(n))
    return CanonicalPlan(tuple((j, k, w, bw) for j, (_, k, w, bw)
                               in enumerate(specs)),
                         pos, pos, (), True)


def canonical_plan(specs: tuple) -> CanonicalPlan:
    """The canonical layout for one exact engine spec tuple
    ((col_index, kind, gather_width, bit_width), ...). Pure and cached —
    safe from any thread."""
    cached = _PLAN_CACHE.get(specs)
    if cached is not None:
        return cached
    n = len(specs)
    if not CANONICALIZE or n == 0:
        plan = _identity_plan(specs)
    else:
        triple = lambda j: (specs[j][1].name, specs[j][2], specs[j][3])
        order = sorted(range(n), key=lambda j: (*triple(j), j))
        groups: list = []  # (kind, w, bw, [dense positions])
        for j in order:
            t = triple(j)
            if groups and groups[-1][0] == t:
                groups[-1][1].append(j)
            else:
                groups.append([t, [j]])
        padded = sum(pad_count(len(members)) for _, members in groups)
        pad = padded <= MAX_SLOTS
        slot_of = [0] * n
        cspecs: list = []
        pack_dense: list = []
        phantom: list = []
        for (_, members) in groups:
            j0 = members[0]
            _, kind, w, bw = specs[j0]
            count = pad_count(len(members)) if pad else len(members)
            for i in range(count):
                slot = len(cspecs)
                cspecs.append((slot, kind, w, bw))
                if i < len(members):
                    slot_of[members[i]] = slot
                    pack_dense.append(members[i])
                else:
                    pack_dense.append(j0)  # donor: same (kind, w, bw)
                    phantom.append(slot)
        identity = not phantom and slot_of == list(range(n))
        plan = CanonicalPlan(tuple(cspecs), tuple(slot_of),
                             tuple(pack_dense), tuple(phantom), identity)
    with _PLAN_LOCK:
        _PLAN_CACHE[specs] = plan
        _LAYOUTS_SEEN.add(plan.specs)
        n_layouts = len(_LAYOUTS_SEEN)
    from ..telemetry.metrics import ETL_DECODE_CANONICAL_LAYOUTS, registry

    registry.gauge_set(ETL_DECODE_CANONICAL_LAYOUTS, n_layouts)
    return plan


# ---------------------------------------------------------------------------
# disk persistence
# ---------------------------------------------------------------------------

_CACHE_FORMAT_VERSION = 1
_DIR_LOCK = threading.Lock()
_CONFIGURED: list = [None]  # [str | None]; None = fall back to env


def configure(cache_dir: "str | None") -> None:
    """Set (or clear) the process-wide program cache directory.
    `Pipeline.start` calls this from `BatchConfig.program_cache_dir`;
    None restores the $ETL_TPU_PROGRAM_CACHE_DIR / disabled default."""
    with _DIR_LOCK:
        _CONFIGURED[0] = cache_dir


def active_dir() -> "str | None":
    with _DIR_LOCK:
        configured = _CONFIGURED[0]
    if configured is not None:
        return configured
    return os.environ.get("ETL_TPU_PROGRAM_CACHE_DIR") or None


def place_jax_compile_cache() -> str:
    """Give JAX's persistent compilation cache a directory that outlives
    the process, and return it. Entry points (chip_smoke.py,
    benchmark/run.py, the replicator) call this once before their first compile; nothing
    calls it at import time. Where $JAX_COMPILATION_CACHE_DIR is set JAX
    already reads it and no code names another directory; otherwise the
    cache lives at `<checkout>/.jax_cache` — a fixed path, because the
    path is part of what a later process must repeat to hit. Separate
    from the AOT store above, which stays off unless configured."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_SOURCE_MODULES = ("bitpack.py", "parsers.py", "parsers_lanes.py",
                   "pallas_kernel.py", "engine.py", "predicate.py",
                   "staging.py")
_VERSION_TAG: list = []  # lazy singleton


def _cpu_features() -> str:
    """Hash of the host CPU's feature flags: the XLA:CPU AOT pitfall this
    guards (machine features recorded at compile time vs the execution
    host) is exactly a cross-machine mismatch, so the flags ride the
    version tag and heterogeneous hosts sharing a cache dir use separate
    subdirectories instead of loading each other's code."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return hashlib.sha256(
                        " ".join(sorted(line.split(":", 1)[1].split()))
                        .encode()).hexdigest()[:16]
    except OSError:
        pass
    import platform

    return platform.machine() or "unknown"


def _source_hash() -> str:
    h = hashlib.sha256()
    base = os.path.dirname(os.path.abspath(__file__))
    for name in _SOURCE_MODULES:
        try:
            with open(os.path.join(base, name), "rb") as f:
                h.update(name.encode())
                h.update(f.read())
        except OSError:
            h.update(f"missing:{name}".encode())
    return h.hexdigest()[:16]


def version_tag() -> str:
    """Subdirectory name under the cache dir; changes whenever anything
    that could make a serialized executable wrong changes — jax/jaxlib
    version, backend, the decode-program source, the host CPU features.
    Stale populations are simply never read again (wipe the dir to
    reclaim space, OPERATIONS.md runbook)."""
    if not _VERSION_TAG:
        import jax
        import jaxlib

        raw = "|".join((
            f"v{_CACHE_FORMAT_VERSION}", jax.__version__,
            jaxlib.__version__, jax.default_backend(), _source_hash(),
            _cpu_features()))
        _VERSION_TAG.append(hashlib.sha256(raw.encode()).hexdigest()[:16])
    return _VERSION_TAG[0]


def _stable_repr(obj) -> str:
    """Deterministic, process-independent rendering of a program-cache
    key (tuples, enums, primitives). Enum identity uses class+name, never
    the interpreter-dependent default repr."""
    if isinstance(obj, enum.Enum):
        return f"{type(obj).__name__}.{obj.name}"
    if isinstance(obj, tuple):
        return "(" + ",".join(_stable_repr(x) for x in obj) + ")"
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return repr(obj)
    return repr(obj)


def fingerprint(key: tuple) -> str:
    return hashlib.sha256(_stable_repr(key).encode()).hexdigest()[:32]


def _path_for(key: tuple, cache_dir: str) -> str:
    return os.path.join(cache_dir, version_tag(), fingerprint(key) + ".prog")


def _execution_devices(key: tuple) -> list:
    """The devices the program behind `key` was compiled for, as the key
    itself says: the host CPU device for a host key (key[-1]), the mesh's
    devices for a sharded device key (the fingerprint in key[3] carries
    their ids), else the one default device. `deserialize_and_load`
    otherwise assumes the default backend and ALL of its devices — a
    single-device program would come back demanding one shard per visible
    device, and a host program would be handed to the accelerator's
    client."""
    import jax

    if key[-1]:
        from .engine import host_cpu_device

        return [host_cpu_device()]
    mesh_fp = key[3]
    if mesh_fp is None:
        return [jax.devices()[0]]
    by_id = {d.id: d for d in jax.devices()}
    return [by_id[i] for i in mesh_fp[2]]


def save(key: tuple, compiled) -> bool:
    """Serialize one AOT-compiled executable to the cache dir. Atomic
    (tmp + rename) so concurrent processes sharing the dir can never
    observe a torn file. Best-effort: any failure logs and returns
    False — persistence never breaks decode."""
    cache_dir = active_dir()
    if cache_dir is None:
        return False
    try:
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = pickle.dumps({
            "format": _CACHE_FORMAT_VERSION, "key": _stable_repr(key),
            "payload": payload, "in_tree": in_tree, "out_tree": out_tree,
        })
        path = _path_for(key, cache_dir)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return True
    except Exception:
        log.warning("failed to persist compiled program (decode continues "
                    "with the in-memory copy)", exc_info=True)
        return False


def try_load(key: tuple, record_absent: bool = True):
    """Load the serialized executable for `key`, or None. A present-but-
    unreadable file (corruption, version skew inside a tag dir, a
    partial write from a dead process) is DELETED and reported as an
    invalid miss — the caller rebuilds cleanly. `record_absent=False`
    suppresses the absent-miss counter for PRE-probes whose miss path
    leads straight into `acquire` (which probes — and counts — again);
    invalid misses always count, they are actionable events."""
    cache_dir = active_dir()
    if cache_dir is None:
        return None
    from jax.experimental import serialize_executable

    from ..telemetry.metrics import (ETL_COMPILE_CACHE_HITS_TOTAL,
                                     ETL_COMPILE_CACHE_LOAD_SECONDS,
                                     ETL_COMPILE_CACHE_MISSES_TOTAL,
                                     registry)

    path = _path_for(key, cache_dir)
    if not os.path.exists(path):
        if record_absent:
            registry.counter_inc(ETL_COMPILE_CACHE_MISSES_TOTAL,
                                 labels={"reason": "absent"})
        return None
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            data = pickle.load(f)
        if data.get("format") != _CACHE_FORMAT_VERSION \
                or data.get("key") != _stable_repr(key):
            raise ValueError("program cache entry does not match its key")
        devices = _execution_devices(key)
        fn = serialize_executable.deserialize_and_load(
            data["payload"], data["in_tree"], data["out_tree"],
            backend=devices[0].client, execution_devices=devices)
    except Exception:
        log.warning("corrupt/stale program cache entry %s; deleting and "
                    "rebuilding", path, exc_info=True)
        try:
            os.unlink(path)
        except OSError:
            pass
        registry.counter_inc(ETL_COMPILE_CACHE_MISSES_TOTAL,
                             labels={"reason": "invalid"})
        return None
    registry.counter_inc(ETL_COMPILE_CACHE_HITS_TOTAL,
                         labels={"layer": "disk"})
    registry.histogram_observe(ETL_COMPILE_CACHE_LOAD_SECONDS,
                               time.perf_counter() - t0)
    return fn


def acquire(key: tuple, builder, example_args: "tuple | None" = None):
    """Resolve a program-cache miss: disk load if possible, else build
    and compile — and persist the executable for the next process.

    `builder()` returns the jitted callable exactly as the engine builds
    it today; `example_args` are the actual dispatch arrays (their
    shapes/dtypes/placement ARE the jit signature, so the AOT lowering
    can never drift from what the call sites pass). Every path counts
    one program build in etl_programs_compiled_total — the counter the
    warm-restart gates assert stays at zero. A compile error raises; a
    serialization failure degrades to the in-memory executable."""
    from ..telemetry.metrics import ETL_PROGRAMS_COMPILED_TOTAL, registry

    fn = try_load(key)
    if fn is not None:
        return fn
    jitted = builder()
    registry.counter_inc(ETL_PROGRAMS_COMPILED_TOTAL)
    if active_dir() is None or example_args is None:
        return jitted
    lowered = jitted.lower(*example_args)
    compiled = lowered.compile()
    problems = persist_contract_violations(key, jitted, lowered,
                                           example_args)
    if problems:
        # the executable still serves THIS process (decode must not
        # regress on a lint result), but it is never persisted: a
        # prewarm on a later process would otherwise load the poisoned
        # program straight from disk with no compile step left to catch
        # it. Fixing the program re-enables persistence on next build.
        log.warning(
            "compiled decode program %s violates IR persist contracts "
            "(%s); serving it memory-only, NOT caching to disk",
            fingerprint(key), "; ".join(problems))
        return compiled
    save(key, compiled)
    return compiled


def persist_contract_violations(key: tuple, jitted, lowered,
                                example_args) -> list:
    """The AOT-persist gate (etl-lint IR tier, satellite of the
    `--programs` pass): the no-host-callback and donation-verified
    contracts, evaluated on the program about to be cached to disk.
    Expected donation is inferred from the cache key — host programs
    (key[-1] is True) and egress programs (their input is still the
    decode fetch's source) never declare donation; device decode
    programs declare it exactly when the backend supports it
    (engine._donation_supported).
    Returns human-readable violation strings; analyzer errors return []
    (the gate must never block decode or persistence on its own bug)."""
    try:
        import jax

        from ..analysis.ir import contracts
        from .egress import is_egress_key
        from .engine import _donation_supported

        problems = []
        jaxpr = jitted.trace(*example_args).jaxpr
        for detail, _msg in contracts.check_host_callback(jaxpr):
            problems.append(f"ir-host-callback: {detail}")
        declared = not key[-1] and not is_egress_key(key) \
            and _donation_supported()
        for detail, _msg in contracts.check_donation(
                lowered.as_text(), declared, jax.default_backend()):
            problems.append(f"ir-donation: {detail}")
        return problems
    except Exception:
        log.warning("IR persist-contract check failed; persisting "
                    "unchecked", exc_info=True)
        return []


# ---------------------------------------------------------------------------
# observed signatures (PR 11 leftover)
# ---------------------------------------------------------------------------
#
# SchemaStore enumeration prewarms the layouts the STORE knows about, at
# the configured row buckets. The workload's actual program population
# is broader: backlog growth seals mega buckets (65536/262144) the
# default buckets never name, and fused-filter programs are per-table.
# Every host dispatch records its key here (first sighting per process;
# one small atomic file per version tag), and `prewarm_pipeline` folds
# the recorded signatures into its enumeration — a restart prewarms
# what the workload actually used, not just what the store implies.

_OBSERVED_FILE = "observed_sigs.pkl"
_OBSERVED_LOCK = threading.Lock()
_OBSERVED_SEEN: set = set()
#: newest-last cap: a pathological signature churn (unbounded DDL
#: variety) ages out the oldest recordings instead of growing the file
_OBSERVED_MAX = 256


def _observed_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, version_tag(), _OBSERVED_FILE)


def load_observed() -> list:
    """The recorded observed signatures (program-cache keys), oldest
    first. Corruption degrades to an empty list + file deletion — the
    same never-fatal stance as the executable cache."""
    cache_dir = active_dir()
    if not cache_dir:
        return []
    path = _observed_path(cache_dir)
    if not os.path.exists(path):
        return []
    try:
        with open(path, "rb") as f:
            data = pickle.load(f)
        if data.get("format") != _CACHE_FORMAT_VERSION:
            raise ValueError("observed-signature file format mismatch")
        return [k for k in data.get("keys", []) if isinstance(k, tuple)]
    except Exception:
        log.warning("corrupt observed-signature file %s; deleting",
                    path, exc_info=True)
        try:
            os.unlink(path)
        except OSError:
            pass
        return []


def record_observed(key: tuple) -> None:
    """Persist one observed host-program signature. Called by the
    engine's dispatch stage per host dispatch: the disarmed cost is one
    set lookup; the first sighting per process pays a small read-merge-
    write of the signature file (atomic tmp+rename — best-effort across
    processes, last-writer-wins). No cache dir = no-op."""
    cache_dir = active_dir()
    if cache_dir is None:
        return
    with _OBSERVED_LOCK:
        if key in _OBSERVED_SEEN:
            return
        _OBSERVED_SEEN.add(key)
    try:
        path = _observed_path(cache_dir)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with _OBSERVED_LOCK:
            merged = [k for k in load_observed() if k != key] + [key]
            merged = merged[-_OBSERVED_MAX:]
            tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
            with open(tmp, "wb") as f:
                pickle.dump({"format": _CACHE_FORMAT_VERSION,
                             "keys": merged}, f)
            os.replace(tmp, path)
    except Exception:
        log.warning("failed to record observed program signature "
                    "(prewarm coverage only; decode continues)",
                    exc_info=True)


def warm_observed_signatures() -> dict:
    """Disk-load the executable of every recorded observed signature not
    already warm in memory. Synchronous — run on an executor. A recorded
    signature whose .prog was wiped/evicted stays cold here and compiles
    via the nonblocking first touch like any other (no decoder exists to
    build from a bare key)."""
    from .engine import _shared_fn_get, _shared_fn_put

    keys = load_observed()
    ready = 0
    missing = 0
    for key in keys:
        if _shared_fn_get(key) is not None:
            ready += 1
            continue
        fn = try_load(key, record_absent=False)
        if fn is not None:
            _shared_fn_put(key, fn)
            ready += 1
        else:
            missing += 1
    return {"observed": len(keys), "observed_ready": ready,
            "observed_missing": missing}


# ---------------------------------------------------------------------------
# prewarm
# ---------------------------------------------------------------------------

#: row-capacity buckets the pipeline prewarm warms per canonical layout:
#: the streaming seal cap's bucket plus the mid-size bucket CDC flushes
#: most often land in. Callers override per deployment
#: (BatchConfig.prewarm_row_buckets).
PREWARM_ROW_BUCKETS = (4096, 16384)


def warm_host_programs(schemas, row_buckets=None, wait: bool = False) -> dict:
    """Warm the host-backend decode programs for `schemas` (deduped by
    canonical layout × row bucket). Synchronous — run it on an executor
    from async code. Disk hits load inline (fast); cold keys kick the
    engine's nonblocking background compile unless `wait`, which
    compiles inline (the chaos runner uses it to seed a cache dir
    deterministically). Returns {"layouts", "ready", "building"}."""
    from .engine import (DeviceDecoder, _host_fn_ready, _shared_fn_get,
                         _host_fn_key)
    from .staging import synthetic_staged_batch

    # note: a key already warm IN MEMORY is counted ready and skipped —
    # nothing new is persisted for it (the in-memory callable may be a
    # lazy jit, which cannot be serialized after the fact). Callers that
    # need a guaranteed DISK seed (the chaos runner, the persistence
    # tests) clear the in-process cache first.
    buckets = tuple(row_buckets) if row_buckets else PREWARM_ROW_BUCKETS
    seen: set = set()
    ready = 0
    building = 0
    for schema in schemas:
        try:
            dec = DeviceDecoder(schema, mesh=None, telemetry=False,
                                device_min_rows=1 << 30,
                                nonblocking_compile=True)
            specs = dec._host_specs()
            if not specs:
                continue
            n_cols = len(schema.replicated_columns)
            for bucket in buckets:
                key = _host_fn_key(bucket, specs, None)
                if key in seen:
                    continue
                seen.add(key)
                if _shared_fn_get(key) is not None:
                    ready += 1
                    continue
                staged = synthetic_staged_batch(n_cols, bucket)
                if wait:
                    value, _ = dec._device_call(staged, specs, host=True)
                    import jax

                    jax.block_until_ready(value)
                    ready += 1
                elif _host_fn_ready(dec, staged, specs):
                    ready += 1
                else:
                    building += 1
        except Exception:
            log.warning("program prewarm failed for %s; its first batches "
                        "decode on the oracle",
                        getattr(schema, "name", schema), exc_info=True)
    return {"layouts": len(seen), "ready": ready, "building": building}


async def prewarm_pipeline(store, batch_config) -> dict:
    """`Pipeline.start`'s program prewarm: enumerate the SchemaStore's
    table schemas and warm their canonical host-program layouts before
    the apply loop sees traffic. Runs on the default executor — never on
    the event loop (the r5-advisor / etl-lint rule the autotune prewarm
    already follows). A fresh pipeline (no stored schemas yet) is a
    no-op; a restarted one reaches its first durable batch on cached
    programs."""
    import asyncio

    if batch_config.program_cache_dir:
        # the store is PROCESS-global (the admission-capacity stance:
        # the first pipeline to configure a dir fixes it); a co-resident
        # pipeline asking for a different dir is a config conflict —
        # keep the first and say so rather than silently re-routing the
        # first pipeline's programs
        current = active_dir()
        if current and current != batch_config.program_cache_dir:
            log.warning(
                "program cache dir already configured to %s for this "
                "process; ignoring %s (the store is process-global — "
                "the first pipeline to configure it wins)",
                current, batch_config.program_cache_dir)
        else:
            configure(batch_config.program_cache_dir)
    prewarm = batch_config.prewarm_programs
    if prewarm is None:
        prewarm = bool(batch_config.program_cache_dir)
    if not prewarm:
        return {}
    schemas = []
    try:
        for tid in await store.get_table_ids_with_schemas():
            s = await store.get_table_schema(tid)
            if s is not None:
                schemas.append(s)
    except Exception:
        log.warning("program prewarm: schema enumeration failed; decode "
                    "warms lazily", exc_info=True)
        return {}
    loop = asyncio.get_running_loop()

    def _warm() -> dict:
        stats = warm_host_programs(schemas,
                                   batch_config.prewarm_row_buckets) \
            if schemas else {"layouts": 0, "ready": 0, "building": 0}
        # fold in the OBSERVED signatures recorded by previous
        # incarnations: the row buckets the workload actually sealed
        # (mega-seal growth, odd flush sizes) and fused-filter programs,
        # neither of which the SchemaStore enumeration can name
        stats.update(warm_observed_signatures())
        return stats

    stats = await loop.run_in_executor(None, _warm)
    log.info("program prewarm: %d schemas -> %s", len(schemas), stats)
    return stats


def reset_for_tests() -> None:
    """Clear the plan cache / layout gauge inputs and the observed-
    signature process guard (tests only; compiled programs live in
    engine._SHARED_FN_CACHE and are untouched)."""
    with _PLAN_LOCK:
        _PLAN_CACHE.clear()
        _LAYOUTS_SEEN.clear()
    with _OBSERVED_LOCK:
        _OBSERVED_SEEN.clear()
