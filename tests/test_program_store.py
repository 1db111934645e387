"""Program-store tests (ISSUE 12): canonical decode-program layouts,
AOT disk persistence, startup prewarm, invalidation, and two-process
cache-dir sharing.

The byte-identity matrix follows the Pallas==XLA differential stance:
the canonical layout (index erasure + kind sort + count padding) must
produce the SAME decoded ColumnarBatch as the exact layout on every
engine and routing path, because column outputs index by schema
position, never by program slot."""

import os
import pickle
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from etl_tpu.models import (ColumnSchema, Oid, ReplicatedTableSchema,
                            TableName, TableSchema)
from etl_tpu.models.pgtypes import CellKind
from etl_tpu.ops import engine as engine_mod
from etl_tpu.ops import program_store
from etl_tpu.ops.engine import DeviceDecoder
from etl_tpu.ops.staging import stage_tuples, synthetic_staged_batch
from etl_tpu.postgres.codec.pgoutput import (TUPLE_NULL, TUPLE_TEXT,
                                             TupleData)
from etl_tpu.telemetry.metrics import (ETL_COMPILE_CACHE_HITS_TOTAL,
                                       ETL_COMPILE_CACHE_MISSES_TOTAL,
                                       ETL_PROGRAMS_COMPILED_TOTAL,
                                       registry)


def make_schema(oids, tid=1):
    return ReplicatedTableSchema.with_all_columns(TableSchema(
        tid, TableName("public", f"t{tid}"),
        tuple(ColumnSchema(f"c{i}", oid) for i, oid in enumerate(oids))))


def tuples_from_texts(rows):
    out = []
    for r in rows:
        kinds = [TUPLE_NULL if v is None else TUPLE_TEXT for v in r]
        vals = [None if v is None else v.encode() for v in r]
        out.append(TupleData(kinds, vals))
    return out


def assert_batches_identical(a, b):
    assert a.num_rows == b.num_rows
    for ca, cb in zip(a.columns, b.columns):
        np.testing.assert_array_equal(ca.validity, cb.validity)
        if ca.is_dense:
            da = np.where(ca.validity, ca.data, 0)
            db = np.where(cb.validity, cb.data, 0)
            if np.issubdtype(da.dtype, np.floating):
                w = np.uint32 if da.dtype == np.float32 else np.uint64
                np.testing.assert_array_equal(da.view(w), db.view(w))
            else:
                np.testing.assert_array_equal(da, db)
        else:
            for i in range(a.num_rows):
                if ca.validity[i]:
                    assert ca.value(i) == cb.value(i)


def decode_exact_and_canonical(schema, staged, **decoder_kw):
    """Decode the SAME staged batch with canonicalization on and off
    (fresh decoders each side, plan cache cleared between)."""
    canon = DeviceDecoder(schema, **decoder_kw).decode(staged)
    old = program_store.CANONICALIZE
    program_store.CANONICALIZE = False
    program_store._PLAN_CACHE.clear()
    try:
        exact = DeviceDecoder(schema, **decoder_kw).decode(staged)
    finally:
        program_store.CANONICALIZE = old
        program_store._PLAN_CACHE.clear()
    return canon, exact


@pytest.fixture(autouse=True)
def _deconfigure_store():
    yield
    program_store.configure(None)


def _specs(*triples):
    """Synthetic engine specs: (col_idx, kind, w, bw) with sequential
    col indices."""
    return tuple((i, k, w, bw) for i, (k, w, bw) in enumerate(triples))


class TestCanonicalPlan:
    def test_pad_count_ladder(self):
        assert [program_store.pad_count(n) for n in (1, 2, 3, 5, 7, 9, 13)] \
            == [1, 2, 3, 6, 8, 12, 16]
        # ≤1.5× steps: padding never adds more than half a group again
        for n in range(1, 257):
            assert n <= program_store.pad_count(n) <= max(2, (3 * n) // 2)

    def test_identity_when_sorted_and_at_bucket(self):
        plan = program_store.canonical_plan(
            _specs((CellKind.I32, 12, 12), (CellKind.I32, 12, 12)))
        assert plan.identity and not plan.phantom_slots
        # index erasure still applies: program specs are positional
        assert plan.specs == ((0, CellKind.I32, 12, 12),
                              (1, CellKind.I32, 12, 12))

    def test_sorts_and_pads(self):
        # 5× I32 (pads to 6) interleaved with one I64
        specs = _specs(*([(CellKind.I32, 12, 12)] * 2
                         + [(CellKind.I64, 20, 20)]
                         + [(CellKind.I32, 12, 12)] * 3))
        plan = program_store.canonical_plan(specs)
        assert plan.n_slots == 7  # 6 I32 slots + 1 I64
        assert len(plan.phantom_slots) == 1
        assert sorted(plan.slot_of) == sorted(
            set(range(plan.n_slots)) - set(plan.phantom_slots))
        # phantom donors carry the group's own triple
        for slot in plan.phantom_slots:
            donor = plan.pack_dense[slot]
            assert specs[donor][1:] == plan.specs[slot][1:]
        # the padded layout is what an actual 6-I32 + 1-I64 table gets
        full = program_store.canonical_plan(
            _specs(*([(CellKind.I32, 12, 12)] * 6
                     + [(CellKind.I64, 20, 20)])))
        assert full.specs == plan.specs

    def test_order_erasure_shares_layout(self):
        a = program_store.canonical_plan(
            _specs((CellKind.I64, 20, 20), (CellKind.F64, 32, 24)))
        b = program_store.canonical_plan(
            _specs((CellKind.F64, 32, 24), (CellKind.I64, 20, 20)))
        assert a.specs == b.specs

    def test_max_slots_falls_back_to_sort_only(self):
        # 52 groups of 5 would pad to 312 slots > 256: no phantoms
        triples = []
        for g in range(52):
            triples += [(CellKind.I32, 4 + 4 * (g % 50), 10)] * 5
        plan = program_store.canonical_plan(_specs(*triples))
        assert plan.n_slots == 260 or plan.n_slots == len(triples)
        assert not plan.phantom_slots

    def test_canonicalize_off_is_identity(self, monkeypatch):
        monkeypatch.setattr(program_store, "CANONICALIZE", False)
        program_store._PLAN_CACHE.clear()
        specs = _specs((CellKind.I64, 20, 20), (CellKind.I32, 12, 12))
        plan = program_store.canonical_plan(specs)
        assert plan.identity and plan.slot_of == (0, 1)
        program_store._PLAN_CACHE.clear()

    def test_host_key_shared_across_permuted_schemas(self):
        d1 = DeviceDecoder(make_schema([Oid.INT8, Oid.FLOAT8, Oid.INT4]),
                           mesh=None)
        d2 = DeviceDecoder(make_schema([Oid.INT4, Oid.INT8, Oid.FLOAT8]),
                           mesh=None)
        assert engine_mod._host_fn_key(256, d1._host_specs()) \
            == engine_mod._host_fn_key(256, d2._host_specs())


MATRIX_OIDS = [Oid.BOOL, Oid.INT2, Oid.INT4, Oid.INT8, Oid.FLOAT4,
               Oid.FLOAT8, Oid.DATE, Oid.TIME, Oid.TIMESTAMP,
               Oid.TIMESTAMPTZ, Oid.TEXT, Oid.NUMERIC]

MATRIX_ROWS = [
    # narrow widths
    ["t", "1", "2", "3", "1.5", "2.5", "2024-01-02", "03:04:05",
     "2024-01-02 03:04:05", "2024-01-02 03:04:05+00", "x", "1.0"],
    # wide widths (different device width buckets per column)
    ["f", "-32768", "-2147483648", "-9223372036854775808",
     "-1.17549e-38", "-2.2250738585072014e-308", "1999-12-31",
     "23:59:59.999999", "9999-12-31 23:59:59.999999",
     "0001-01-01 00:00:00+15:59", "long text value " * 4,
     "-123456.789012"],
    [None] * 12,
    ["t", "7", "8", "9", "0.0", "-0.0", "2000-02-29", "00:00:00",
     "1970-01-01 00:00:00", "2024-06-01 12:00:00-08", "", "0"],
]


class TestCanonicalByteIdentity:
    """Canonical == exact, proven the way Pallas == XLA is."""

    @pytest.mark.parametrize("engine", ["xla", "pallas"])
    def test_kind_width_matrix(self, engine):
        schema = make_schema(MATRIX_OIDS)
        staged = stage_tuples(
            tuples_from_texts(MATRIX_ROWS * 64), len(MATRIX_OIDS))
        canon, exact = decode_exact_and_canonical(
            schema, staged, device_min_rows=0, mesh=None,
            use_pallas=engine == "pallas")
        assert_batches_identical(canon, exact)

    def test_host_path_matrix(self):
        schema = make_schema(MATRIX_OIDS)
        staged = stage_tuples(
            tuples_from_texts(MATRIX_ROWS * 32), len(MATRIX_OIDS))
        canon, exact = decode_exact_and_canonical(
            schema, staged, device_min_rows=1 << 30, host_min_rows=1,
            mesh=None)
        assert_batches_identical(canon, exact)

    def test_phantom_padding_byte_identity(self):
        # 5 same-(kind, width) columns pad to 6 slots (device specs are
        # data-dependent, so the 5 columns must carry equal-width text
        # to land in one canonical group)
        oids = [Oid.INT4] * 5 + [Oid.INT8]
        schema = make_schema(oids)
        rows = [[str(100 + i % 800), str(100 + (i * 7) % 800),
                 None if i % 5 == 0 else str(200 + i % 700),
                 str(100 + (i * 3) % 800), str(999 - i % 800),
                 None if i % 7 == 0 else str(i * 1000)]
                for i in range(200)]
        staged = stage_tuples(tuples_from_texts(rows), len(oids))
        dec = DeviceDecoder(schema, device_min_rows=0, mesh=None)
        specs = dec._specs(staged, dec._widths(staged))
        plan = program_store.canonical_plan(specs)
        assert plan.phantom_slots, "scenario must actually pad"
        canon, exact = decode_exact_and_canonical(
            schema, staged, device_min_rows=0, mesh=None)
        assert_batches_identical(canon, exact)

    def test_nibble_path_with_phantoms(self):
        # all-nibble kinds (ints/dates) keep the nibble fast path with
        # phantom slots zeroed after the pack
        oids = [Oid.INT4] * 5 + [Oid.DATE]
        schema = make_schema(oids)
        rows = [[str(100 + i), str(101 + i), str(102 + i), str(103 + i),
                 str(104 + i), "2024-03-0%d" % (1 + i % 9)]
                for i in range(100)]
        staged = stage_tuples(tuples_from_texts(rows), len(oids))
        dec = DeviceDecoder(schema, device_min_rows=0, mesh=None)
        packed = dec._pack_stage(
            staged, dec._specs(staged, dec._widths(staged)))
        assert packed.nibble, "scenario must exercise the nibble pack"
        assert packed.plan is not None and packed.plan.phantom_slots
        canon, exact = decode_exact_and_canonical(
            schema, staged, device_min_rows=0, mesh=None)
        assert_batches_identical(canon, exact)

    def test_oracle_fallback_rows_identical(self):
        # oversized-width values (valid via leading zeros) force CPU
        # fixup through the canonical unpack path
        oids = [Oid.INT4, Oid.INT4, Oid.TEXT]
        schema = make_schema(oids)
        rows = [["0" * 57 + str(100 + i), str(i), "v"]
                for i in range(150)]
        staged = stage_tuples(tuples_from_texts(rows), 3)
        canon, exact = decode_exact_and_canonical(
            schema, staged, device_min_rows=0, mesh=None)
        assert_batches_identical(canon, exact)

    def test_mesh_8shard_byte_identity(self):
        """Canonical == exact under an 8-way forced host-platform mesh
        (subprocess: the device count is fixed at backend init)."""
        code = r"""
import numpy as np
from tests.test_program_store import (decode_exact_and_canonical,
                                      assert_batches_identical,
                                      make_schema, tuples_from_texts)
from etl_tpu.ops.staging import stage_tuples
from etl_tpu.models import Oid
from etl_tpu.parallel.mesh import decode_mesh

oids = [Oid.INT4] * 5 + [Oid.INT8, Oid.FLOAT8]
schema = make_schema(oids)
rows = [[str(i), str(i*3), None, "77", str(-i), str(i*1000), "1.5"]
        for i in range(1024)]
staged = stage_tuples(tuples_from_texts(rows), len(oids))
mesh = decode_mesh()
assert mesh is not None and mesh.size == 8
canon, exact = decode_exact_and_canonical(
    schema, staged, device_min_rows=0, mesh=mesh, mesh_min_rows=0)
assert_batches_identical(canon, exact)
print("MESH_CANONICAL_OK")
"""
        repo = Path(__file__).resolve().parent.parent
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=300,
                              cwd=repo, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "MESH_CANONICAL_OK" in proc.stdout


_TEXT_BY_KIND = {
    CellKind.BOOL: lambda i: "t" if i % 2 else "f",
    CellKind.DATE: lambda i: "2024-03-%02d" % (1 + i % 28),
    CellKind.TIME: lambda i: "03:04:%02d" % (i % 60),
    CellKind.TIMESTAMP: lambda i: "2024-01-02 03:04:%02d" % (i % 60),
    CellKind.TIMESTAMPTZ: lambda i: "2024-01-02 03:04:%02d+00" % (i % 60),
    CellKind.NUMERIC: lambda i: "%d.25" % i,
    CellKind.F32: lambda i: "%d.5" % i,
    CellKind.F64: lambda i: "%d.5" % i,
}


def _decode_once(schema, tmp_cache, rows=None):
    """One host-path decode against a configured cache dir; returns the
    batch and the decoder. The canonical host key is evicted from the
    in-process cache FIRST — earlier tests in the suite may share the
    same canonical layout (that sharing is the feature), and these
    tests specifically exercise the compile/persist/load path, so every
    call must behave like a fresh process."""
    program_store.configure(str(tmp_cache))
    oids = [c.type_oid for c in schema.replicated_columns]
    kinds = [c.kind for c in schema.replicated_columns]
    rows = rows or [[_TEXT_BY_KIND.get(k, lambda i: str(i))(i)
                     for k in kinds] for i in range(128)]
    staged = stage_tuples(tuples_from_texts(rows), len(oids))
    dec = DeviceDecoder(schema, device_min_rows=1 << 30, host_min_rows=1,
                        mesh=None)
    _evict_keys([engine_mod._host_fn_key(staged.row_capacity,
                                         dec._host_specs(), None)])
    return dec.decode(staged), dec


def _evict_keys(keys):
    with engine_mod._SHARED_FN_LOCK:
        for k in keys:
            engine_mod._SHARED_FN_CACHE.pop(k, None)


class TestPersistence:
    def test_save_load_roundtrip_zero_compiles(self, tmp_path):
        schema = make_schema([Oid.INT8, Oid.INT4], tid=41)
        b1, dec = _decode_once(schema, tmp_path)
        assert registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL) > 0
        # simulate a fresh process: evict the compiled program
        _evict_keys(dec._fn_cache)
        c0 = registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL)
        h0 = registry.get_counter(ETL_COMPILE_CACHE_HITS_TOTAL,
                                  {"layer": "disk"})
        b2, _ = _decode_once(schema, tmp_path)
        assert registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL) == c0, \
            "warm restart must compile ZERO fresh programs"
        assert registry.get_counter(ETL_COMPILE_CACHE_HITS_TOTAL,
                                    {"layer": "disk"}) == h0 + 1
        assert_batches_identical(b1, b2)

    def test_reload_binds_the_devices_the_key_names(self, tmp_path):
        """jax 0.9.0's deserialize_and_load defaults to the default
        backend and ALL of its devices: with the suite's 8 forced host
        devices a reloaded single-device program demanded 8 shards
        ("Expected args to execute_sharded_on_local_devices to have 8
        shards"). Store a host-key, a device-key and a mesh-key program,
        reload each into an emptied in-process cache, and call it with
        the arguments its dispatch passes."""
        import jax

        from etl_tpu.parallel.mesh import decode_mesh

        assert len(jax.devices()) == 8
        program_store.configure(str(tmp_path))
        schema = make_schema([Oid.INT4, Oid.INT8], tid=45)
        staged = stage_tuples(tuples_from_texts(
            [[str(i), str(i * 1000)] for i in range(300)]), 2)
        cases = (
            ("host", dict(device_min_rows=1 << 30, host_min_rows=1,
                          mesh=None)),
            ("device", dict(device_min_rows=0, mesh=None)),
            ("mesh", dict(device_min_rows=0, mesh=decode_mesh(),
                          mesh_min_rows=0)),
        )
        for name, kw in cases:
            dec = DeviceDecoder(schema, **kw)
            first = dec.decode(staged)
            keys = list(dec._fn_cache)
            assert [k[-1] for k in keys] == [name == "host"]
            assert [k[3] is not None for k in keys] == [name == "mesh"]
            # the program may have been in memory already (layouts are
            # shared across the suite): evict, decode again so THIS
            # call compiles and persists, then evict and reload
            _evict_keys(keys)
            dec.decode(staged)
            _evict_keys(keys)
            c0 = registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL)
            h0 = registry.get_counter(ETL_COMPILE_CACHE_HITS_TOTAL,
                                      {"layer": "disk"})
            again = DeviceDecoder(schema, **kw).decode(staged)
            assert registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL) == c0, \
                f"{name}: reload fell back to a rebuild"
            assert registry.get_counter(ETL_COMPILE_CACHE_HITS_TOTAL,
                                        {"layer": "disk"}) == h0 + 1, name
            assert_batches_identical(first, again)

    def test_corrupt_file_degrades_to_rebuild(self, tmp_path):
        schema = make_schema([Oid.INT8, Oid.DATE], tid=42)
        b1, dec = _decode_once(schema, tmp_path)
        progs = list(Path(tmp_path).rglob("*.prog"))
        assert progs
        for p in progs:
            p.write_bytes(b"garbage")
        _evict_keys(dec._fn_cache)
        i0 = registry.get_counter(ETL_COMPILE_CACHE_MISSES_TOTAL,
                                  {"reason": "invalid"})
        c0 = registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL)
        b2, _ = _decode_once(schema, tmp_path)
        assert registry.get_counter(ETL_COMPILE_CACHE_MISSES_TOTAL,
                                    {"reason": "invalid"}) > i0
        assert registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL) > c0
        assert_batches_identical(b1, b2)
        # the rebuild re-persisted a VALID entry
        _evict_keys(dec._fn_cache)
        c1 = registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL)
        _decode_once(schema, tmp_path)
        assert registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL) == c1

    def test_key_mismatch_treated_as_invalid(self, tmp_path):
        schema = make_schema([Oid.INT8, Oid.INT2], tid=43)
        _, dec = _decode_once(schema, tmp_path)
        key = next(iter(dec._fn_cache))
        path = Path(program_store._path_for(key, str(tmp_path)))
        data = pickle.loads(path.read_bytes())
        data["key"] = "somebody else's key"
        path.write_bytes(pickle.dumps(data))
        assert program_store.try_load(key) is None
        assert not path.exists(), "mismatched entry must be deleted"

    def test_version_tag_invalidation(self, monkeypatch, tmp_path):
        import jaxlib

        t0 = program_store.version_tag()
        # jaxlib upgrade → different tag (old population never read)
        monkeypatch.setattr(jaxlib, "__version__", "99.99.99")
        monkeypatch.setattr(program_store, "_VERSION_TAG", [])
        t1 = program_store.version_tag()
        assert t1 != t0
        # decode-source change → different tag
        monkeypatch.setattr(program_store, "_VERSION_TAG", [])
        monkeypatch.setattr(program_store, "_source_hash",
                            lambda: "feedfacefeedface")
        t2 = program_store.version_tag()
        assert t2 not in (t0, t1)

    def test_fingerprint_stability_and_separation(self):
        key1 = engine_mod._host_fn_key(
            256, DeviceDecoder(make_schema([Oid.INT8]),
                               mesh=None)._host_specs())
        assert program_store.fingerprint(key1) \
            == program_store.fingerprint(key1)
        # mesh fingerprint in the slot separates keys (the PR 8
        # contract, now extended to disk)
        base = (256, key1[1], False, None, False, None, False)
        meshed = (256, key1[1], False, (("sp",), (8,), tuple(range(8))),
                  False, None, False)
        assert program_store.fingerprint(base) \
            != program_store.fingerprint(meshed)

    def test_stable_repr_renders_enums_by_name(self):
        s = program_store._stable_repr(
            (1, (CellKind.I64, 20), None, True, "x"))
        assert "CellKind.I64" in s and "None" in s

    def test_unconfigured_store_never_touches_disk(self, tmp_path):
        program_store.configure(None)
        # no env var in tests → no disk layer: try_load/save are no-ops
        if os.environ.get("ETL_TPU_PROGRAM_CACHE_DIR"):
            pytest.skip("cache dir forced by environment")
        key = ("k",)
        assert program_store.try_load(key) is None
        assert program_store.save(key, object()) is False

    def test_two_process_cache_dir_sharing(self, tmp_path):
        """Two concurrent processes share one dir (atomic writes); a
        third incarnation loads with zero compiles."""
        code = r"""
import sys
from tests.test_program_store import make_schema, _decode_once
from etl_tpu.telemetry.metrics import ETL_PROGRAMS_COMPILED_TOTAL, registry
from etl_tpu.models import Oid

schema = make_schema([Oid.INT8, Oid.TIMESTAMP], tid=44)
_decode_once(schema, sys.argv[1])
print("COMPILED=%d" % registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL))
"""
        repo = Path(__file__).resolve().parent.parent
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen(
            [sys.executable, "-c", code, str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=repo, env=env) for _ in range(2)]
        outs = [p.communicate(timeout=300) for p in procs]
        assert all(p.returncode == 0 for p in procs), outs
        progs = list(Path(tmp_path).rglob("*.prog"))
        assert progs and not list(Path(tmp_path).rglob("*.tmp.*"))
        third = subprocess.run(
            [sys.executable, "-c", code, str(tmp_path)],
            capture_output=True, text=True, timeout=300, cwd=repo, env=env)
        assert third.returncode == 0, third.stderr[-2000:]
        assert "COMPILED=0" in third.stdout


class TestPrewarm:
    def _seed(self, tmp_path, schema):
        program_store.configure(str(tmp_path))
        dec = DeviceDecoder(schema, mesh=None)
        key = engine_mod._host_fn_key(256, dec._host_specs(), None)
        # evict BEFORE seeding: earlier suite tests may share this
        # canonical layout, and a memory-hot key would make the seed a
        # no-op instead of writing the disk entry under test
        _evict_keys([key])
        stats = program_store.warm_host_programs(
            [schema], row_buckets=(256,), wait=True)
        assert stats["layouts"] == 1
        # fresh-process simulation
        _evict_keys([key])
        return key

    def test_host_fn_ready_loads_from_disk(self, tmp_path):
        schema = make_schema([Oid.INT8, Oid.NUMERIC], tid=51)
        key = self._seed(tmp_path, schema)
        dec = DeviceDecoder(schema, mesh=None, nonblocking_compile=True)
        staged = synthetic_staged_batch(2, 256)
        c0 = registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL)
        assert engine_mod._host_fn_ready(dec, staged, dec._host_specs()) \
            is True, "disk-warm key must be READY, not background-compiled"
        assert engine_mod.background_compiles_inflight() == 0
        assert registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL) == c0
        assert engine_mod._shared_fn_get(key) is not None

    def test_prewarm_pipeline_from_schema_store(self, tmp_path):
        import asyncio

        from etl_tpu.config import BatchConfig
        from etl_tpu.store import NotifyingStore

        schema = make_schema([Oid.INT8, Oid.INT4, Oid.FLOAT8], tid=52)
        key = self._seed(tmp_path, schema)

        async def go():
            store = NotifyingStore()
            await store.store_table_schema(schema, 0)
            return await program_store.prewarm_pipeline(
                store, BatchConfig(program_cache_dir=str(tmp_path),
                                   prewarm_row_buckets=(256,)))

        c0 = registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL)
        stats = asyncio.run(go())
        assert stats == {"layouts": 1, "ready": 1, "building": 0,
                         "observed": 1, "observed_ready": 1,
                         "observed_missing": 0}
        assert registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL) == c0
        assert engine_mod._shared_fn_get(key) is not None

    def test_prewarm_pipeline_empty_store_noop(self, tmp_path):
        import asyncio

        from etl_tpu.config import BatchConfig
        from etl_tpu.store import NotifyingStore

        async def go():
            return await program_store.prewarm_pipeline(
                NotifyingStore(),
                BatchConfig(program_cache_dir=str(tmp_path)))

        assert asyncio.run(go()) == {"layouts": 0, "ready": 0,
                                     "building": 0, "observed": 0,
                                     "observed_ready": 0,
                                     "observed_missing": 0}

    def test_prewarm_auto_disabled_without_cache_dir(self):
        import asyncio

        from etl_tpu.config import BatchConfig
        from etl_tpu.store import NotifyingStore

        async def go():
            return await program_store.prewarm_pipeline(
                NotifyingStore(), BatchConfig())

        assert asyncio.run(go()) == {}

    def test_prewarm_dedupes_canonical_layouts(self, tmp_path):
        """N permuted-column tables warm ONE layout — the compile-storm
        fix for many-table pipelines."""
        program_store.configure(str(tmp_path))
        schemas = [make_schema(o, tid=60 + i) for i, o in enumerate([
            [Oid.INT8, Oid.INT4, Oid.FLOAT8],
            [Oid.FLOAT8, Oid.INT8, Oid.INT4],
            [Oid.INT4, Oid.FLOAT8, Oid.INT8]])]
        stats = program_store.warm_host_programs(
            schemas, row_buckets=(256,), wait=True)
        assert stats["layouts"] == 1
