"""Workload diversity matrix (ISSUE 7): generator determinism per
profile, a pgoutput decode round-trip per profile, the non-insert
invariant-checker semantics, the fake walsender's ALTER storage rewrite,
the nonblocking decode compile, and the chaos x workload tier-1 matrix.

Acceptance: one (profile, seed) pair replays a byte-identical WAL
payload sequence; the chaos corpus subset (incl. crash->restart and
stall) passes the invariant checker on >=4 non-insert profiles with
bit-identical --seed replay per (scenario, profile, seed).
"""

from __future__ import annotations

import asyncio
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from etl_tpu.chaos.corpus import (WORKLOAD_MATRIX, WORKLOAD_MATRIX_PROFILES,
                                  get_scenario)
from etl_tpu.chaos.invariants import reconstruct_final_view
from etl_tpu.chaos.runner import run_scenario
from etl_tpu.chaos.scenario import FaultKind, Scenario
from etl_tpu.models.cell import TOAST_UNCHANGED
from etl_tpu.models.event import (DeleteEvent, InsertEvent, TruncateEvent,
                                  UpdateEvent)
from etl_tpu.models.pgtypes import Oid
from etl_tpu.models.schema import (ColumnSchema, ReplicatedTableSchema,
                                   TableName, TableSchema)
from etl_tpu.models.table_row import PartialTableRow, TableRow
from etl_tpu.postgres.codec.pgoutput import (TUPLE_NULL,
                                             TUPLE_UNCHANGED_TOAST,
                                             DeleteMessage, InsertMessage,
                                             RelationMessage,
                                             TruncateMessage, TupleData,
                                             UpdateMessage,
                                             decode_logical_message)
from etl_tpu.postgres.codec.text import parse_cell_text
from etl_tpu.postgres.fake import FakeDatabase
from etl_tpu.workloads import (PROFILES, WorkloadGenerator, get_profile,
                               profile_names, wal_payloads)

SEED = 11
ALL_PROFILES = profile_names()


async def _drive(name: str, seed: int, steps: int = 6) -> WorkloadGenerator:
    gen = WorkloadGenerator(name, seed=seed)
    gen.db = db = gen.build_db()
    for _ in range(steps):
        await gen.run_tx(db)
    return gen


class TestCatalog:
    def test_profile_breadth(self):
        """The catalog covers every traffic axis the issue names."""
        assert len(PROFILES) >= 10
        by = {n: get_profile(n) for n in ALL_PROFILES}
        assert any(p.update_weight > p.insert_weight for p in by.values())
        assert any(p.delete_weight >= 0.4 for p in by.values())
        assert any(p.replica_identity == "f" for p in by.values())
        assert any(len(p.columns()) >= 100 for p in by.values())
        assert any(p.toast_unchanged_rate > 0 for p in by.values())
        assert any(p.truncate_every for p in by.values())
        assert any(p.ddl_every for p in by.values())
        assert any(p.partitioned for p in by.values())
        assert any(p.rows_per_tx >= 256 for p in by.values())
        assert any(p.txs_per_step >= 4 and p.rows_per_tx == 1
                   for p in by.values())

    def test_unknown_profile_names_known(self):
        with pytest.raises(KeyError, match="update_heavy_default"):
            get_profile("no_such_profile")


class TestDeterminism:
    @pytest.mark.parametrize("name", ALL_PROFILES)
    async def test_byte_identical_replay(self, name):
        """Same (profile, seed) -> byte-identical WAL payload sequence,
        including the commit timestamps (the pinned clock)."""
        a = await _drive(name, SEED)
        b = await _drive(name, SEED)
        assert wal_payloads(a.db) == wal_payloads(b.db)
        assert a.expected == b.expected

    async def test_seed_changes_the_stream(self):
        a = await _drive("update_heavy_default", 1)
        b = await _drive("update_heavy_default", 2)
        assert wal_payloads(a.db) != wal_payloads(b.db)

    async def test_stressors_fire_once_per_step_not_per_tx(self):
        """truncate_every/ddl_every are per STEP: a multi-transaction
        step carries the stressor only in its first transaction."""
        from dataclasses import replace

        from etl_tpu.workloads.profiles import PROFILES

        p = replace(PROFILES["truncate_storm"], name="truncate_multi_tx",
                    txs_per_step=4, truncate_every=2)
        gen = WorkloadGenerator(p, seed=SEED)
        db = gen.build_db()
        for _ in range(4):
            await gen.run_tx(db)
        truncates = sum(
            1 for payload in wal_payloads(db)
            if isinstance(decode_logical_message(payload),
                          TruncateMessage))
        # steps 0..3 with truncate_every=2 -> exactly step 2 truncates
        # (step 0 is exempt), ONCE despite 4 transactions in the step
        assert truncates == 1


def _reference_apply(payloads, initial):
    """A reference pgoutput consumer: decode every WAL payload and apply
    it to {rel_id: {pk: tuple(parsed values)}}, starting from the copied
    seed rows. Deliberately independent of the pipeline's codec/event.py
    so the round-trip test cross-checks the generator's own bookkeeping
    rather than re-deriving it through the same code."""
    rels: dict[int, RelationMessage] = {}
    tables = {tid: dict(rows) for tid, rows in initial.items()}

    def parse(tup: TupleData, rid: int, prev=None):
        cols = rels[rid].columns
        out = []
        for i, c in enumerate(cols):
            kind = tup.kinds[i]
            if kind == TUPLE_UNCHANGED_TOAST:
                assert prev is not None, "unchanged TOAST without old row"
                out.append(prev[i])
            elif kind == TUPLE_NULL:
                out.append(None)
            else:
                out.append(parse_cell_text(tup.values[i].decode(),
                                           c.type_oid))
        return tuple(out)

    def pk_of(tup: TupleData, rid: int):
        c0 = rels[rid].columns[0]
        return parse_cell_text(tup.values[0].decode(), c0.type_oid)

    for payload in payloads:
        m = decode_logical_message(payload)
        if isinstance(m, RelationMessage):
            rels[m.relation_id] = m
            tables.setdefault(m.relation_id, {})
        elif isinstance(m, InsertMessage):
            row = parse(m.new_tuple, m.relation_id)
            tables[m.relation_id][row[0]] = row
        elif isinstance(m, UpdateMessage):
            rid = m.relation_id
            old = m.old_tuple or m.key_tuple
            old_pk = pk_of(old, rid) if old is not None else None
            new_pk = pk_of(m.new_tuple, rid)
            prev = tables[rid].get(old_pk if old_pk is not None else new_pk)
            row = parse(m.new_tuple, rid, prev=prev)
            if old_pk is not None and old_pk != row[0]:
                tables[rid].pop(old_pk, None)
            tables[rid][row[0]] = row
        elif isinstance(m, DeleteMessage):
            tup = m.old_tuple or m.key_tuple
            tables[m.relation_id].pop(pk_of(tup, m.relation_id), None)
        elif isinstance(m, TruncateMessage):
            for rid in m.relation_ids:
                tables.get(rid, {}).clear()
    return tables


class TestDecodeRoundTrip:
    @pytest.mark.parametrize("name", ALL_PROFILES)
    async def test_pgoutput_roundtrip(self, name):
        """Decoding the generated WAL with an independent pgoutput
        consumer reconstructs exactly the generator's committed truth:
        old-tuple identity under DEFAULT vs FULL, unchanged-TOAST
        markers, truncate fan-out, DDL relation re-sends, and
        partitioned leaf->root attribution all survive the wire."""
        gen = WorkloadGenerator(name, seed=SEED)
        db = gen.build_db()
        initial = {tid: dict(rows) for tid, rows in gen.expected.items()}
        for _ in range(8):
            await gen.run_tx(db)
        got = _reference_apply(wal_payloads(db), initial)
        for tid in gen.table_ids:
            view = got.get(tid, {})
            if gen.row_filter is not None:
                # filter-offload profiles: the WAL carries EVERY row (the
                # walsender does not filter); the delivery contract is the
                # reference state restricted to predicate-passing rows
                pred = gen.row_filter.compile_values(gen._schemas[tid])
                view = {pk: row for pk, row in view.items() if pred(row)}
            assert view == gen.expected[tid], \
                f"{name}: table {tid} diverged"

    async def test_old_tuple_identity_shape(self):
        """DEFAULT ships key-only 'K' tuples exactly when the PK changes
        (or on delete); FULL always ships the full 'O' old image."""
        for name, want_key, want_old in (
                ("update_heavy_default", True, False),
                ("update_heavy_full", False, True)):
            gen = await _drive(name, SEED, steps=8)
            saw_update_old = saw_key = saw_old = False
            for payload in wal_payloads(gen.db):
                m = decode_logical_message(payload)
                if isinstance(m, UpdateMessage):
                    saw_key |= m.key_tuple is not None
                    saw_old |= m.old_tuple is not None
                    saw_update_old |= (m.key_tuple or m.old_tuple) \
                        is not None
                elif isinstance(m, DeleteMessage) and m.old_tuple:
                    saw_old = True
            assert saw_update_old
            assert saw_key == want_key, name
            assert saw_old == want_old, name

    async def test_toast_profile_sends_unchanged_markers(self):
        gen = await _drive("toast_heavy_full", SEED, steps=8)
        kinds = [k for p in wal_payloads(gen.db)
                 for m in [decode_logical_message(p)]
                 if isinstance(m, UpdateMessage)
                 for k in m.new_tuple.kinds]
        assert TUPLE_UNCHANGED_TOAST in kinds


def _schema(tid=500, ncols=3):
    cols = [ColumnSchema("id", Oid.INT8, nullable=False,
                         primary_key_ordinal=1)]
    cols += [ColumnSchema(f"c{i}", Oid.TEXT) for i in range(ncols - 1)]
    return ReplicatedTableSchema.with_all_columns(
        TableSchema(tid, TableName("public", "inv"), tuple(cols)))


class _Dest:
    """The minimal destination surface reconstruct_final_view reads."""

    def __init__(self, events, table_rows=None):
        self.events = events
        self.table_rows = table_rows or {}


def _ins(s, lsn, ordinal, values):
    return InsertEvent(lsn, lsn, ordinal, s, TableRow(values))


def _upd(s, lsn, ordinal, values, old=None):
    return UpdateEvent(lsn, lsn, ordinal, s, TableRow(values),
                       old_row=old)


def _del(s, lsn, ordinal, key):
    return DeleteEvent(lsn, lsn, ordinal, s,
                       PartialTableRow(key, [v is not None for v in key]))


class TestInvariantCheckerNonInsert:
    """Regression for the ISSUE 7 satellite: reconstruct_final_view used
    to keep only the highest-ranked event per pk and treat every row as
    an upsert — correct for insert-CDC, wrong for deletes-then-reinserts,
    PK-changing updates, unchanged-TOAST patches, and truncates."""

    def test_delete_then_reinsert_survives(self):
        s = _schema()
        view = reconstruct_final_view(_Dest([
            _ins(s, 10, 0, [1, "a", "b"]),
            _del(s, 20, 0, [1, None, None]),
            _ins(s, 30, 0, [1, "a2", "b2"]),
        ]), [s.id])
        assert view[s.id] == {1: (1, "a2", "b2")}

    def test_pk_changing_update_removes_old_key(self):
        s = _schema()
        view = reconstruct_final_view(_Dest([
            _ins(s, 10, 0, [1, "a", "b"]),
            _upd(s, 20, 0, [2, "a", "b"],
                 old=PartialTableRow([1, None, None],
                                     [True, False, False])),
        ]), [s.id])
        assert view[s.id] == {2: (2, "a", "b")}

    def test_unchanged_toast_patches_column_wise(self):
        s = _schema()
        view = reconstruct_final_view(_Dest([
            _ins(s, 10, 0, [1, "fat-value", "b"]),
            _upd(s, 20, 0, [1, TOAST_UNCHANGED, "b2"]),
        ]), [s.id])
        assert view[s.id] == {1: (1, "fat-value", "b2")}

    def test_truncate_clears_copied_baseline_and_prior_events(self):
        s = _schema()
        dest = _Dest([
            _ins(s, 10, 0, [2, "x", "y"]),
            TruncateEvent(20, 20, 0, 0, (s,)),
            _ins(s, 30, 0, [3, "z", "w"]),
        ], table_rows={s.id: [TableRow([1, "seed", "row"])]})
        view = reconstruct_final_view(dest, [s.id])
        assert view[s.id] == {3: (3, "z", "w")}

    def test_rekey_update_with_unchanged_toast_patches_from_old_key(self):
        """A PK-changing update carrying TOAST_UNCHANGED: the stored
        value (the patch source) lives under the OLD key — popping it
        first must not lose it."""
        s = _schema()
        view = reconstruct_final_view(_Dest([
            _ins(s, 10, 0, [1, "fat-value", "b"]),
            _upd(s, 20, 0, [2, TOAST_UNCHANGED, "b2"],
                 old=PartialTableRow([1, None, None],
                                     [True, False, False])),
        ]), [s.id])
        assert view[s.id] == {2: (2, "fat-value", "b2")}

    def test_wal_rank_beats_delivery_order(self):
        """At-least-once redelivery can re-send an old window AFTER newer
        events; replay must follow (commit_lsn, tx_ordinal), not arrival."""
        s = _schema()
        newer = _upd(s, 30, 0, [1, "new", "b"])
        older = _upd(s, 20, 0, [1, "old", "b"])
        view = reconstruct_final_view(_Dest([
            _ins(s, 10, 0, [1, "a", "b"]), newer, older, newer,
        ]), [s.id])
        assert view[s.id] == {1: (1, "new", "b")}


class TestFakeAlterStorageRewrite:
    """Regression for the forced fake fix: ALTER TABLE with column
    changes must rewrite stored rows onto the new column list — without
    it, a post-ALTER delete under identity FULL shipped an old image at
    the pre-ALTER width against the post-ALTER RELATION message."""

    async def test_post_alter_old_images_match_relation_width(self):
        db = FakeDatabase()
        base = TableSchema(600, TableName("public", "t"), (
            ColumnSchema("id", Oid.INT8, nullable=False,
                         primary_key_ordinal=1),
            ColumnSchema("v", Oid.TEXT)))
        db.create_table(base, rows=[["1", "a"], ["2", "b"]])
        db.create_publication("pub", [600])
        db.set_replica_identity(600, "f")
        widened = TableSchema(600, TableName("public", "t"),
                              base.columns + (ColumnSchema("x", Oid.TEXT),))
        async with db.transaction() as tx:
            tx.alter_table(600, widened)
            tx.delete(600, ["2", None, None])
        msgs = [decode_logical_message(p) for p in wal_payloads(db)]
        rel = next(m for m in reversed(msgs)
                   if isinstance(m, RelationMessage))
        del_msg = next(m for m in msgs if isinstance(m, DeleteMessage))
        assert len(rel.columns) == 3
        assert len(del_msg.old_tuple) == 3
        # the added column backfills as NULL in the rewritten storage
        assert del_msg.old_tuple.kinds[2] == TUPLE_NULL


class TestNonblockingCompile:
    async def test_cold_program_routes_oracle_then_host(self):
        """nonblocking_compile: the first batch of a cold (bucket, specs)
        key decodes on the oracle while the host program compiles on a
        background thread; once the build lands, batches route host —
        and both paths decode to identical cells."""
        from etl_tpu.ops import engine as eng
        from etl_tpu.ops.staging import stage_tuples

        schema = ReplicatedTableSchema.with_all_columns(TableSchema(
            700, TableName("public", "nb"), (
                ColumnSchema("id", Oid.INT8, nullable=False,
                             primary_key_ordinal=1),
                ColumnSchema("a", Oid.INT4),
                ColumnSchema("b", Oid.INT8))))
        tuples = [TupleData([ord("t")] * 3,
                            [str(i).encode(), str(i * 2).encode(),
                             str(i * 3).encode()])
                  for i in range(8)]
        dec = eng.DeviceDecoder(schema, device_min_rows=10**9,
                                host_min_rows=1,
                                nonblocking_compile=True)
        staged = stage_tuples(tuples, 3)
        mode0, _ = dec._route(staged)
        first = dec.decode(stage_tuples(tuples, 3))
        for _ in range(600):  # the build is seconds at worst on 3 cols
            if eng.background_compiles_inflight() == 0:
                break
            await asyncio.sleep(0.05)
        assert eng.background_compiles_inflight() == 0
        mode1, _ = dec._route(stage_tuples(tuples, 3))
        assert (mode0, mode1) == ("oracle", "host")
        second = dec.decode(stage_tuples(tuples, 3))
        assert first.to_rows() == second.to_rows()

    def test_streaming_decoders_are_nonblocking(self):
        """The two streaming construction sites opt in (a 120-column
        first-touch compile measured 32s on this container — inline it
        wedges the apply loop past the stall deadline)."""
        import inspect

        from etl_tpu.runtime import assembler, copy

        assert "nonblocking_compile=True" in inspect.getsource(
            assembler.EventAssembler._seal_run)
        assert "nonblocking_compile=True" in \
            inspect.getsource(copy.parallel_table_copy)


class TestChaosWorkloadMatrix:
    def test_matrix_shape_meets_acceptance(self):
        """>=4 non-insert profiles, at least one crash->restart base and
        one stall base."""
        non_insert = {s.workload for s in WORKLOAD_MATRIX
                      if get_profile(s.workload).insert_weight < 1.0}
        assert len(non_insert) >= 4
        assert len(set(WORKLOAD_MATRIX_PROFILES)) >= 4
        kinds = {f.kind for s in WORKLOAD_MATRIX for f in s.faults}
        assert FaultKind.CRASH in kinds
        assert FaultKind.STALL in kinds
        for s in WORKLOAD_MATRIX:
            assert s.workload in PROFILES

    @pytest.mark.parametrize("scenario", WORKLOAD_MATRIX,
                             ids=lambda s: s.name)
    async def test_matrix_invariants_green(self, scenario):
        run = await run_scenario(scenario, SEED)
        assert run.ok, run.describe()
        assert run.describe()["workload"] == scenario.workload

    async def test_replay_bit_identical_per_triple(self):
        """(scenario, profile, seed) -> identical injection trace,
        resume LSNs, and delivered end state."""
        scenario = get_scenario("crash_mid_apply__update_heavy_full")
        a = await run_scenario(scenario, 42)
        b = await run_scenario(scenario, 42)
        assert a.ok and b.ok
        assert a.trace == b.trace
        assert [r.resume_lsn for r in a.restarts] == \
            [r.resume_lsn for r in b.restarts]

    def test_cli_workload_replayed_in_manifest(self):
        """`python -m etl_tpu.chaos --workload P --seed N` twice:
        manifests identify the profile and replay bit-identically."""
        repo = Path(__file__).resolve().parent.parent
        outs = []
        for _ in range(2):
            proc = subprocess.run(
                [sys.executable, "-m", "etl_tpu.chaos", "--seed", "5",
                 "--scenario", "wire_disconnect_mid_cdc",
                 "--workload", "delete_heavy_default"],
                capture_output=True, text=True, timeout=240, cwd=repo)
            assert proc.returncode == 0, proc.stderr[-2000:]
            d = json.loads(proc.stdout.strip().splitlines()[-1])
            assert d["ok"] is True
            assert d["workload"] == "delete_heavy_default"
            outs.append((d["trace"],
                         [{k: v for k, v in r.items() if k != "recovery_s"}
                          for r in d["restarts"]]))
        assert outs[0] == outs[1]


class TestBenchWiring:
    """A profile through the whole `Pipeline` with no fault injected:
    the chaos runner's plain drive, held to its end-state check."""

    @staticmethod
    def _plain(profile: str, txs: int) -> Scenario:
        return Scenario(name=f"plain__{profile}",
                        description="no faults: deliver and verify",
                        workload=profile, txs=txs)

    async def test_workload_streaming_verifies_end_state(self):
        """Delivered AND verified: the sink's reconstructed final view
        equals the generator's committed truth, with no restart and no
        fault to explain a difference."""
        run = await run_scenario(self._plain("delete_heavy_default", 24),
                                 SEED)
        assert run.ok, run.describe()
        assert run.restarts == [] and run.fault_firings == 0
        stats = run.report.stats
        assert stats["lost_rows"] == 0 and stats["expected_rows"] > 0
        assert stats["delivered_events"] >= 120
        assert stats["max_duplication"] == 1

    async def test_workload_streaming_reports_verification_failure(
            self, monkeypatch):
        """A destination view that never matches the committed truth
        comes back as a failed run that has shut its pipeline down — not
        a hang, not an unhandled TimeoutError."""
        from etl_tpu import workloads
        from etl_tpu.chaos.invariants import _pipeline_thread_count
        from etl_tpu.chaos.runner import _wait_until

        threads = _pipeline_thread_count()
        monkeypatch.setattr(workloads.WorkloadGenerator, "delivered",
                            lambda self, dest: False)
        run = await run_scenario(self._plain("insert_heavy", 6), SEED,
                                 timeout_s=4.0)
        assert not run.ok
        assert any("did not complete" in v
                   for v in run.report.violations), run.report.violations
        await _wait_until(lambda: _pipeline_thread_count() <= threads,
                          2.0, "pipeline threads lingering")


class TestReviewRegressions:
    def test_failed_background_compile_does_not_respawn(self):
        """A deterministically-failing host-program build is remembered:
        later batches of the same signature stay on the oracle without
        spawning a fresh compile thread per batch."""
        from etl_tpu.ops import engine as eng
        from etl_tpu.ops.staging import stage_tuples

        schema = ReplicatedTableSchema.with_all_columns(TableSchema(
            701, TableName("public", "bgfail"), (
                ColumnSchema("id", Oid.INT8, nullable=False,
                             primary_key_ordinal=1),
                ColumnSchema("a", Oid.INT4))))
        dec = eng.DeviceDecoder(schema, device_min_rows=10**9,
                                host_min_rows=1, nonblocking_compile=True)
        dec._device_call = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("simulated XLA build failure"))
        tuples = [TupleData([ord("t")] * 2,
                            [str(i).encode(), str(i).encode()])
                  for i in range(4)]
        staged = stage_tuples(tuples, 2)
        specs = dec._host_specs()
        key = eng._host_fn_key(staged.row_capacity, specs)
        with eng._SHARED_FN_LOCK:  # earlier tests may have compiled it
            eng._SHARED_FN_CACHE.pop(key, None)
        try:
            assert eng._host_fn_ready(dec, staged, specs) is False
            for _ in range(200):  # the doomed build fails fast
                if eng.background_compiles_inflight() == 0:
                    break
                time.sleep(0.02)
            with eng._BG_COMPILE_LOCK:
                assert key in eng._BG_COMPILE_FAILED
            threads_before = threading.active_count()
            for _ in range(5):
                assert eng._host_fn_ready(dec, staged, specs) is False
            assert threading.active_count() <= threads_before
            assert dec._route(staged)[0] == "oracle"
        finally:
            with eng._BG_COMPILE_LOCK:
                eng._BG_COMPILE_FAILED.discard(key)

    def test_cli_workload_rejects_matrix_entry_scenario(self):
        """--workload over a matrix entry would mislabel the manifest
        (the entry's name pins its profile); the CLI must refuse."""
        repo = Path(__file__).resolve().parent.parent
        proc = subprocess.run(
            [sys.executable, "-m", "etl_tpu.chaos",
             "--scenario", "crash_mid_apply__update_heavy_default",
             "--workload", "ddl_churn"],
            capture_output=True, text=True, timeout=120, cwd=repo)
        assert proc.returncode == 2
        assert "pins the profile" in proc.stderr
