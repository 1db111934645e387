"""Fused publication row filtering: differential tests.

The fused coerce→filter→transpose program (ISSUE 11 / ROADMAP item 4)
must produce BYTE-IDENTICAL compacted output across every lowering —
the XLA jnp.where-mask twin, the Pallas fused kernel (interpret mode on
this CPU backend), the mesh-sharded per-shard compaction (8 forced host
shards via conftest), and the per-row host oracle — and its verdicts
must equal the predicate IR's pure-python evaluators on every CellKind.

Fallback machinery is adversarially covered: escape rows, oversized
fields, and device-unparseable values are force-kept by the device and
re-judged on host AFTER oracle fixup, with all bookkeeping living in the
compacted index space.
"""

import datetime as dt
import random

import numpy as np
import pytest

from etl_tpu.models import (ColumnSchema, Oid, ReplicatedTableSchema,
                            TableName, TableSchema)
from etl_tpu.models.lsn import Lsn
from etl_tpu.ops import DeviceDecoder, stage_copy_chunk, stage_tuples
from etl_tpu.ops.predicate import (And, Cmp, Not, NullTest, Or, RowFilter,
                                   RowFilterError, compile_row_filter,
                                   parse_row_filter)
from etl_tpu.postgres.codec.pgoutput import (TUPLE_NULL, TUPLE_TEXT,
                                             TupleData)
from etl_tpu.testing.batches import batches_identical

rng = random.Random(1234)


def make_rts(cols, row_filter=None):
    rts = ReplicatedTableSchema.with_all_columns(TableSchema(
        1, TableName("public", "t"),
        tuple(ColumnSchema(f"c{i}", oid) for i, oid in enumerate(cols))))
    if row_filter is not None:
        rts = rts.with_row_predicate(parse_row_filter(row_filter))
    return rts


def stage_texts(rows, n_cols):
    tuples = []
    for r in rows:
        kinds = [TUPLE_NULL if v is None else TUPLE_TEXT for v in r]
        vals = [None if v is None else v.encode() for v in r]
        tuples.append(TupleData(kinds, vals))
    return stage_tuples(tuples, n_cols)


def oracle_decoder(rts):
    """Every row through the per-row CPU oracle, filter via host_keep —
    the reference the fused paths must match bit for bit."""
    return DeviceDecoder(rts, device_min_rows=10**9, host_min_rows=10**9,
                         mesh=None)


def decode_all_engines(rts, staged):
    """(xla, pallas, host-XLA, oracle) filtered batches for one input."""
    xla = DeviceDecoder(rts, device_min_rows=0, mesh=None).decode(staged)
    pal = DeviceDecoder(rts, device_min_rows=0, mesh=None,
                        use_pallas=True).decode(staged)
    host = DeviceDecoder(rts, device_min_rows=10**9, host_min_rows=1,
                         mesh=None).decode(staged)
    orc = oracle_decoder(rts).decode(staged)
    return xla, pal, host, orc


def assert_all_identical(rts, staged, expected_survivors=None):
    xla, pal, host, orc = decode_all_engines(rts, staged)
    assert batches_identical(xla, pal), "pallas != xla"
    assert batches_identical(xla, host), "host-XLA != xla"
    assert batches_identical(xla, orc), "oracle != xla"
    if expected_survivors is not None:
        assert xla.source_rows is not None
        assert list(xla.source_rows) == list(expected_survivors)
    return xla


# ---------------------------------------------------------------------------
# parser + IR
# ---------------------------------------------------------------------------


class TestRowFilterParser:
    def test_roundtrip_json_and_fingerprint(self):
        rf = parse_row_filter(
            "(v < 10 AND note IS NOT NULL) OR NOT flag = TRUE")
        back = RowFilter.from_json(rf.to_json())
        assert back == rf
        assert back.fingerprint() == rf.fingerprint()
        assert set(rf.referenced_columns()) == {"v", "note", "flag"}

    def test_precedence_and_parens(self):
        rf = parse_row_filter("a = 1 OR b = 2 AND c = 3")
        assert isinstance(rf.root, Or)
        assert isinstance(rf.root.items[1], And)
        rf2 = parse_row_filter("(a = 1 OR b = 2) AND c = 3")
        assert isinstance(rf2.root, And)

    def test_quoted_identifiers_and_strings(self):
        rf = parse_row_filter("\"odd col\" = 'it''s'")
        assert rf.root == Cmp("eq", "odd col", "it's")

    def test_is_null_forms(self):
        assert parse_row_filter("x IS NULL").root == NullTest("x", False)
        assert parse_row_filter("x IS NOT NULL").root == NullTest("x", True)
        assert parse_row_filter("NOT x IS NULL").root \
            == Not(NullTest("x", False))

    def test_pg_catalog_paren_wrapping(self):
        # pg_publication_tables wraps rowfilter text in parens
        rf = parse_row_filter("(v < 42)")
        assert rf.root == Cmp("lt", "v", 42)

    def test_unsupported_sql_raises(self):
        for sql in ("v + 1 < 2", "lower(note) = 'x'", "v IN (1,2)",
                    "v BETWEEN 1 AND 2", "v < ", "((v < 1)"):
            with pytest.raises(RowFilterError):
                parse_row_filter(sql)

    def test_unknown_column_fails_at_compile(self):
        rts = make_rts([Oid.INT4])
        with pytest.raises(RowFilterError):
            compile_row_filter("missing < 1", rts)

    @pytest.mark.parametrize("sql", [
        "c1 > 0.5",                     # non-integral vs int column
        "c2 > '2024-01-01T00:00:00'",   # ISO 'T' — codec can't parse
    ])
    def test_pg_valid_but_unrepresentable_literal_degrades(self, sql):
        """PG accepts these filters; the client envelope cannot represent
        them. Binding must fail as RowFilterError (never a raw codec
        error), and the decoder must degrade to UNFILTERED decode with a
        warning — not raise per batch (review finding: a crash here
        killed the apply loop)."""
        rts = make_rts([Oid.INT8, Oid.INT4, Oid.TIMESTAMP], sql)
        with pytest.raises(RowFilterError):
            compile_row_filter(rts.row_predicate, rts)
        rows = [[str(i), str(i - 5),
                 f"2024-06-15 12:00:0{i % 10}"] for i in range(100)]
        staged = stage_texts(rows, 3)
        batch = DeviceDecoder(rts, device_min_rows=0, mesh=None) \
            .decode(staged)
        assert batch.num_rows == 100
        assert batch.source_rows is None

    def test_filtered_profile_rejects_mutating_mix(self):
        import dataclasses

        from etl_tpu.workloads import WorkloadGenerator
        from etl_tpu.workloads.profiles import get_profile

        bad = dataclasses.replace(get_profile("filter_selective_50"),
                                  update_weight=0.3)
        with pytest.raises(ValueError, match="insert-only"):
            WorkloadGenerator(bad, seed=1)


class TestKleeneSemantics:
    def test_null_comparisons_are_unknown(self):
        schema = TableSchema(1, TableName("p", "t"),
                             (ColumnSchema("v", Oid.INT4),
                              ColumnSchema("w", Oid.INT4)))
        allows = parse_row_filter("v < 10 OR w < 10").compile_texts(schema)
        assert allows(["5", None])
        assert allows([None, "5"])
        assert not allows([None, None])
        assert not allows([None, "50"])  # F OR U = U -> not published
        neg = parse_row_filter("NOT v = 1").compile_texts(schema)
        assert not neg([None, None])  # NOT U = U

    def test_is_null_is_two_valued(self):
        schema = TableSchema(1, TableName("p", "t"),
                             (ColumnSchema("v", Oid.INT4),))
        allows = parse_row_filter("v IS NULL").compile_texts(schema)
        assert allows([None]) and not allows(["1"])


# ---------------------------------------------------------------------------
# differential across every device-comparable CellKind (+ host-path kinds)
# ---------------------------------------------------------------------------


def _rand_ts(frac=True):
    base = (f"2024-{rng.randrange(1, 13):02d}-{rng.randrange(1, 29):02d} "
            f"{rng.randrange(24):02d}:{rng.randrange(60):02d}"
            f":{rng.randrange(60):02d}")
    if frac and rng.random() < 0.7:
        base += f".{rng.randrange(10**6):06d}"
    return base


KIND_CASES = [
    # (oid, value_renderer, sql_literal for a mid-split comparison)
    (Oid.BOOL, lambda: rng.choice(["t", "f"]), "TRUE"),
    (Oid.INT2, lambda: str(rng.randrange(-32768, 32768)), "0"),
    (Oid.INT4, lambda: str(rng.randrange(-2**31, 2**31)), "12345"),
    (Oid.OID, lambda: str(rng.randrange(0, 2**32)), "2147483648"),
    (Oid.INT8, lambda: str(rng.randrange(-2**63, 2**63)),
     "-1234567890123"),
    (Oid.DATE, lambda: f"{rng.randrange(1, 9999):04d}-"
                       f"{rng.randrange(1, 13):02d}-"
                       f"{rng.randrange(1, 29):02d}", "'2024-06-15'"),
    (Oid.TIME, lambda: f"{rng.randrange(24):02d}:{rng.randrange(60):02d}"
                       f":{rng.randrange(60):02d}"
                       f".{rng.randrange(10**6):06d}", "'12:00:00'"),
    (Oid.TIMESTAMP, _rand_ts, "'2024-06-15 12:00:00'"),
    (Oid.TIMESTAMPTZ,
     lambda: _rand_ts() + rng.choice(["+00", "-05", "+09:30", "+02:00"]),
     "'2024-06-15 12:00:00+00'"),
]


class TestDifferentialAllKinds:
    @pytest.mark.parametrize("op", ["<", "=", ">=", "<>"])
    @pytest.mark.parametrize(
        "oid,render,literal", KIND_CASES,
        ids=["bool", "i16", "i32", "u32", "i64", "date", "time", "ts",
             "tstz"])
    def test_device_kinds_match_oracle_and_python_truth(
            self, oid, render, literal, op):
        rts = make_rts([Oid.INT8, oid], f"c1 {op} {literal}")
        rows = [[str(i), None if rng.random() < 0.08 else render()]
                for i in range(300)]
        staged = stage_texts(rows, 2)
        allows = rts.row_predicate.compile_texts(rts.table_schema)
        expected = [i for i, r in enumerate(rows) if allows(r)]
        crf = compile_row_filter(rts.row_predicate, rts)
        assert crf.device_supported
        assert_all_identical(rts, staged, expected)

    @pytest.mark.parametrize("oid,render,literal", [
        (Oid.FLOAT8, lambda: f"{rng.randrange(-10**6, 10**6)}"
                             f".{rng.choice(('0', '25', '5', '75'))}",
         "0.5"),
        (Oid.NUMERIC, lambda: f"{rng.randrange(0, 10**9)}"
                              f".{rng.randrange(100):02d}", "500000000"),
        (Oid.TEXT, lambda: rng.choice(["alpha", "beta", "gamma"]),
         "'beta'"),
    ], ids=["F64", "NUMERIC", "TEXT"])
    def test_host_path_kinds_filter_via_host_keep(self, oid, render,
                                                  literal):
        """Predicates over kinds outside the device envelope fall back to
        the post-decode host mask — correct on every route, just without
        the fetch win."""
        rts = make_rts([Oid.INT8, oid], f"c1 = {literal}")
        crf = compile_row_filter(rts.row_predicate, rts)
        assert not crf.device_supported
        rows = [[str(i), None if rng.random() < 0.08 else render()]
                for i in range(300)]
        staged = stage_texts(rows, 2)
        allows = rts.row_predicate.compile_texts(rts.table_schema)
        expected = [i for i, r in enumerate(rows) if allows(r)]
        assert_all_identical(rts, staged, expected)

    def test_compound_predicate(self):
        rts = make_rts(
            [Oid.INT8, Oid.INT4, Oid.DATE],
            "(c1 >= 0 AND c1 < 500000) OR c2 > '2024-06-01' "
            "OR c1 IS NULL")
        rows = [[str(i),
                 None if rng.random() < 0.1
                 else str(rng.randrange(-10**6, 10**6)),
                 f"2024-{rng.randrange(1, 13):02d}-"
                 f"{rng.randrange(1, 29):02d}"]
                for i in range(512)]
        staged = stage_texts(rows, 3)
        allows = rts.row_predicate.compile_texts(rts.table_schema)
        expected = [i for i, r in enumerate(rows) if allows(r)]
        assert expected, "degenerate predicate"
        assert_all_identical(rts, staged, expected)


# ---------------------------------------------------------------------------
# selectivity edges
# ---------------------------------------------------------------------------


class TestSelectivityEdges:
    def _staged(self, n=400):
        rows = [[str(i), str(rng.randrange(-1000, 1000))]
                for i in range(n)]
        return rows, stage_texts(rows, 2)

    def test_zero_survivors(self):
        _, staged = self._staged()
        batch = assert_all_identical(
            make_rts([Oid.INT8, Oid.INT4], "c1 < -5000"), staged, [])
        assert batch.num_rows == 0

    def test_all_survive(self):
        rows, staged = self._staged()
        assert_all_identical(
            make_rts([Oid.INT8, Oid.INT4], "c1 >= -1000"), staged,
            list(range(len(rows))))

    def test_single_survivor(self):
        rows, staged = self._staged()
        batch = assert_all_identical(
            make_rts([Oid.INT8, Oid.INT8], "c0 = 123"), staged, [123])
        assert batch.columns[0].data[0] == 123

    def test_all_rows_fallback_bc_dates(self):
        """Every referenced value is device-unparseable (BC dates): the
        device force-keeps everything, the oracle fixup decodes, and the
        host re-check applies the predicate exactly."""
        rows = [[str(i), f"{rng.randrange(1, 500):04d}-06-15 BC"]
                for i in range(96)]
        staged = stage_texts(rows, 2)
        rts = make_rts([Oid.INT8, Oid.DATE], "c1 < '0300-01-01 BC'")
        allows = rts.row_predicate.compile_texts(rts.table_schema)
        expected = [i for i, r in enumerate(rows) if allows(r)]
        assert 0 < len(expected) < len(rows)
        assert_all_identical(rts, staged, expected)


class TestFetchedBytes:
    """What the fusion is for: a filtered program fetches the survivors,
    not the batch. A count of bytes (etl_decode_fetched_bytes_total),
    never a time."""

    #: over the measured keep fraction: the keep mask (1 bit/row), the
    #: survivor-count words and staging.slice_rows' fetch granularity
    #: (max(R/16, 256) rows)
    SLACK = 0.11
    N = 4096

    @staticmethod
    def _fetched(dec, staged):
        from etl_tpu.telemetry.metrics import (
            ETL_DECODE_FETCHED_BYTES_TOTAL, registry)

        before = registry.get_counter(ETL_DECODE_FETCHED_BYTES_TOTAL)
        batch = dec.decode(staged)
        return batch, \
            registry.get_counter(ETL_DECODE_FETCHED_BYTES_TOTAL) - before

    @pytest.mark.parametrize("keep", [0.1, 0.5, 0.9])
    def test_filtered_fetch_is_bounded_by_selectivity(self, keep):
        vals = np.random.RandomState(11).randint(-10**6, 10**6, self.N)
        staged = stage_texts(
            [[str(i), str(int(v)), f"n-{i}"] for i, v in enumerate(vals)],
            3)
        cols = [Oid.INT8, Oid.INT4, Oid.TEXT]
        threshold = int(-10**6 + 2 * 10**6 * keep)
        sql = f"c1 < {threshold}"
        want = int((vals < threshold).sum())
        # the device program and its host-XLA twin, each against its own
        # unfiltered program
        for label, route in (
                ("xla", dict(device_min_rows=1)),
                ("host", dict(device_min_rows=10**9, host_min_rows=1))):
            _, plain = self._fetched(
                DeviceDecoder(make_rts(cols), mesh=None, **route), staged)
            batch, filtered = self._fetched(
                DeviceDecoder(make_rts(cols, sql), mesh=None, **route),
                staged)
            assert batch.num_rows == want, label
            assert plain > 0, label
            assert filtered / plain <= want / self.N + self.SLACK, \
                f"{label}: fetched {filtered} of {plain} bytes at " \
                f"keep {want / self.N:.3f}"


# ---------------------------------------------------------------------------
# fallback bookkeeping in the compacted index space
# ---------------------------------------------------------------------------


class TestFallbackRemap:
    def test_copy_escape_rows_fix_up_at_compacted_indices(self):
        """COPY rows with escapes land in cpu_fallback_rows → force-keep;
        after compaction their fixup (and its unescaped values) must land
        at the COMPACTED positions."""
        lines = []
        vals = []
        for i in range(300):
            v = rng.randrange(-1000, 1000)
            vals.append(v)
            note = f"a\\tb{i}" if i % 7 == 0 else f"plain{i}"
            lines.append(f"{i}\t{v}\t{note}")
        staged = stage_copy_chunk(("\n".join(lines) + "\n").encode(), 3)
        assert len(staged.cpu_fallback_rows) > 0
        rts = make_rts([Oid.INT8, Oid.INT4, Oid.TEXT], "c1 < 0")
        batch = assert_all_identical(rts, staged)
        expected = [i for i, v in enumerate(vals) if v < 0]
        assert list(batch.source_rows) == expected
        for pos, src in enumerate(batch.source_rows):
            want = f"a\tb{src}" if src % 7 == 0 else f"plain{src}"
            assert batch.columns[2].value(pos) == want

    def test_oversized_referenced_field_forces_host_recheck(self):
        """A referenced int wider than the host gather width (zero-padded
        '+000…123') is device-untrustworthy: force-keep + fixup + host
        re-evaluation must keep/drop it on its TRUE value."""
        rows = []
        for i in range(128):
            if i % 5 == 0:
                # 24 chars > the I32 host gather width (12); true value
                # alternates around the threshold
                v = "+" + "0" * 20 + (f"{i:03d}" if i % 2 == 0
                                      else f"-{i:02d}".replace("-", "9"))
            else:
                v = str(rng.randrange(-1000, 1000))
            rows.append([str(i), v])
        staged = stage_texts(rows, 2)
        rts = make_rts([Oid.INT8, Oid.INT4], "c1 < 0")
        allows = rts.row_predicate.compile_texts(rts.table_schema)
        expected = [i for i, r in enumerate(rows) if allows(r)]
        xla = DeviceDecoder(rts, device_min_rows=10**9, host_min_rows=1,
                            mesh=None).decode(staged)
        orc = oracle_decoder(rts).decode(staged)
        assert batches_identical(xla, orc)
        assert list(xla.source_rows) == expected

    def test_update_runs_are_never_filtered(self):
        """allow_row_filter=False (the assembler's stance for runs with
        updates/deletes) must bypass filtering entirely."""
        rows = [[str(i), str(-100)] for i in range(200)]
        staged = stage_texts(rows, 2)
        staged.allow_row_filter = False
        rts = make_rts([Oid.INT8, Oid.INT4], "c1 > 0")
        batch = DeviceDecoder(rts, device_min_rows=0, mesh=None) \
            .decode(staged)
        assert batch.num_rows == 200
        assert batch.source_rows is None


# ---------------------------------------------------------------------------
# mesh identity (8 forced host shards via conftest XLA_FLAGS)
# ---------------------------------------------------------------------------


class TestMeshShardedIdentity:
    def test_filtered_mesh_equals_single_device(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs the conftest 8-device host platform")
        from etl_tpu.parallel.mesh import decode_mesh

        mesh = decode_mesh()
        rows = [[str(i),
                 None if rng.random() < 0.1
                 else str(rng.randrange(-10**6, 10**6))]
                for i in range(3000)]
        staged = stage_texts(rows, 2)
        rts = make_rts([Oid.INT8, Oid.INT4], "c1 < 0 OR c1 IS NULL")
        single = DeviceDecoder(rts, device_min_rows=0, mesh=None) \
            .decode(staged)
        sharded = DeviceDecoder(rts, device_min_rows=0, mesh=mesh,
                                mesh_min_rows=0).decode(staged)
        assert batches_identical(single, sharded)
        allows = rts.row_predicate.compile_texts(rts.table_schema)
        expected = [i for i, r in enumerate(rows) if allows(r)]
        assert list(sharded.source_rows) == expected
        assert 0 < single.num_rows < 3000


# ---------------------------------------------------------------------------
# event/assembler integration: identity arrays compact in lockstep
# ---------------------------------------------------------------------------


class TestEventArrayCompaction:
    def _assemble(self, payload_rows, rts):
        """(events, assembler) — the caller must resolve every event's
        batch BEFORE closing the assembler (close fences the pipeline's
        queued-but-undispatched jobs, the production teardown contract)."""
        from etl_tpu.config.pipeline import BatchEngine
        from etl_tpu.postgres.codec.pgoutput import encode_insert
        from etl_tpu.runtime.assembler import EventAssembler

        asm = EventAssembler(BatchEngine.TPU)
        for i, vals in enumerate(payload_rows):
            payload = encode_insert(
                1, [None if v is None else v.encode() for v in vals])
            asm.push_raw_row(payload, rts, Lsn(1000 + i), Lsn(9999), i)
        return asm.flush(), asm

    def test_change_arrays_slice_to_survivors(self):
        rts = make_rts([Oid.INT8, Oid.INT4], "c1 < 0")
        vals = [str(rng.randrange(-1000, 1000)) for _ in range(200)]
        events, asm = self._assemble(
            [[str(i), v] for i, v in enumerate(vals)], rts)
        try:
            (ev,) = events
            pre_len = len(ev.change_types)
            batch = ev.batch  # resolves + compacts the identity arrays
            expected = [i for i, v in enumerate(vals) if int(v) < 0]
            assert batch.num_rows == len(expected) < pre_len
            assert len(ev.change_types) == len(ev.commit_lsns) \
                == len(ev.tx_ordinals) == len(expected)
            assert list(ev.tx_ordinals) == expected
            assert list(batch.columns[0].data) == expected
        finally:
            asm.close()

    def test_unfiltered_schema_unchanged(self):
        rts = make_rts([Oid.INT8, Oid.INT4])
        events, asm = self._assemble(
            [[str(i), str(i)] for i in range(100)], rts)
        try:
            (ev,) = events
            assert ev.batch.num_rows == 100
            assert len(ev.change_types) == 100
        finally:
            asm.close()


# ---------------------------------------------------------------------------
# pipelined path == serial path
# ---------------------------------------------------------------------------


class TestPipelinedFiltering:
    def test_pipeline_submit_matches_serial(self):
        from etl_tpu.ops import DecodePipeline

        rts = make_rts([Oid.INT8, Oid.INT4], "c1 >= 250")
        rows = [[str(i), str(i)] for i in range(1000)]
        dec = DeviceDecoder(rts, device_min_rows=0, mesh=None)
        serial = dec.decode(stage_texts(rows, 2))
        pipe = DecodePipeline(window=2)
        try:
            handles = [pipe.submit(dec, stage_texts(rows, 2))
                       for _ in range(3)]
            for h in handles:
                got = h.result()
                assert batches_identical(serial, got)
                assert list(got.source_rows) == list(range(250, 1000))
        finally:
            pipe.close()


# ---------------------------------------------------------------------------
# schema / serialization plumbing
# ---------------------------------------------------------------------------


class TestSchemaPlumbing:
    def test_replicated_schema_json_roundtrip_with_filter(self):
        rts = make_rts([Oid.INT8, Oid.INT4], "c1 < 7")
        back = ReplicatedTableSchema.from_json(rts.to_json())
        assert back.row_predicate == rts.row_predicate
        assert back == rts  # filter is not part of schema equality

    def test_with_row_predicate_identity_preserving(self):
        rts = make_rts([Oid.INT8])
        assert rts.with_row_predicate(None) is rts
        rf = parse_row_filter("c0 = 1")
        rts2 = rts.with_row_predicate(rf)
        assert rts2.with_row_predicate(rf) is rts2

    def test_table_cache_attaches_predicates(self):
        from etl_tpu.runtime.table_cache import SharedTableCache

        cache = SharedTableCache()
        rts = make_rts([Oid.INT8, Oid.INT4])
        cache.set(rts)
        cache.set_row_predicates({1: parse_row_filter("c1 < 5")})
        assert cache.get(1).row_predicate is not None
        # RELATION re-send without a predicate re-attaches it
        cache.set(make_rts([Oid.INT8, Oid.INT4]))
        assert cache.get(1).row_predicate is not None

    def test_fake_source_surfaces_predicate(self):
        import asyncio

        from etl_tpu.postgres.fake import FakeDatabase, FakeSource

        schema = TableSchema(
            77, TableName("public", "ft"),
            (ColumnSchema("id", Oid.INT8, nullable=False,
                          primary_key_ordinal=1),
             ColumnSchema("v", Oid.INT4)))
        db = FakeDatabase()
        db.create_table(schema)
        db.create_publication(
            "pub", [77], row_filters={77: ("v < 9", lambda r: True)})
        src = FakeSource(db)
        got = asyncio.run(src.get_table_schema(77, "pub"))
        assert got.row_predicate is not None
        assert got.row_predicate.sql == "v < 9"
        assert asyncio.run(src.get_row_filters("pub")) == {77: "v < 9"}

    def test_offload_mode_walsender_stops_filtering(self):
        from etl_tpu.postgres.fake import FakeDatabase

        db = FakeDatabase()
        db.create_publication("pub", [5],
                              row_filters={5: lambda r: False})
        assert not db.row_filter_allows("pub", 5, ["x"])
        db.server_row_filtering = False
        assert db.row_filter_allows("pub", 5, ["x"])
