"""Horizontal scale-out: shard map, slots, store surface, shard-scoped
runtime, two-phase rebalancing, sharded chaos, and orchestration.

Covers ISSUE 9's acceptance bars in-tree:
  - ShardMap determinism + minimal movement (HRW properties);
  - parse_slot_name right-anchored parsing round-trips every slot shape
    (property-tested), including the new `_s{shard}` suffixes;
  - the StateStore shard-assignment surface (memory + sqlite), epoch
    monotonicity, and the ShardScopedStore ownership/epoch write fence;
  - K=2 sharded pipelines over ONE fake source: per-shard delivery,
    delivery isolation, sibling tables never purged;
  - ShardCoordinator K=2→3: the fence-LSN handoff loses nothing;
  - the chaos pod-kill scenario;
  - shard-aware K8s/local orchestration fan-out.
"""

from __future__ import annotations

import asyncio
import random

import pytest

from etl_tpu.models.errors import ErrorKind, EtlError
from etl_tpu.postgres.slots import (ParsedSlot, apply_slot_name,
                                    parse_slot_name, slots_for_pipeline,
                                    table_sync_slot_name)
from etl_tpu.sharding import (ShardAssignment, ShardMap, moved_tables)
from etl_tpu.sharding.runtime import ShardIdentity, ShardScopedStore

TABLES_1K = list(range(16384, 17384))


# ---------------------------------------------------------------------------
# ShardMap properties
# ---------------------------------------------------------------------------


class TestShardMap:
    def test_deterministic_across_instances_and_seeds(self):
        """The map is a pure function of (table_id, K): fresh instances,
        shuffled input order, and different epochs all agree — and a
        subprocess (fresh interpreter, different PYTHONHASHSEED) agrees
        byte for byte, so K pods can each compute it locally."""
        a, b = ShardMap(4), ShardMap(4, epoch=9)
        shuffled = list(TABLES_1K)
        random.Random(3).shuffle(shuffled)
        for tid in shuffled:
            assert a.shard_of(tid) == b.shard_of(tid)

        import json
        import subprocess
        import sys

        script = (
            "import json;from etl_tpu.sharding import ShardMap;"
            "m=ShardMap(4);"
            "print(json.dumps([m.shard_of(t) for t in range(16384,16484)]))")
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            env={"PYTHONHASHSEED": "12345", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu"},
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-500:]
        assert json.loads(proc.stdout) == \
            [a.shard_of(t) for t in range(16384, 16484)]

    @pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
    def test_every_table_lands_in_range(self, k):
        m = ShardMap(k)
        for tid in TABLES_1K[:200]:
            assert 0 <= m.shard_of(tid) < k

    @pytest.mark.parametrize("k", [2, 3, 4, 7])
    def test_grow_moves_about_one_over_k_plus_one(self, k):
        """K→K+1 re-homes ≈ 1/(K+1) of tables (binomial tolerance over
        1000 tables), every moved table moves TO the new shard, and no
        unmoved table changes its index."""
        old, new = ShardMap(k), ShardMap(k + 1)
        moved = moved_tables(old, new, TABLES_1K)
        frac = len(moved) / len(TABLES_1K)
        ideal = 1 / (k + 1)
        assert 0.6 * ideal <= frac <= 1.5 * ideal, \
            f"K={k}: moved {frac:.3f}, ideal {ideal:.3f}"
        for tid, (src, dst) in moved.items():
            assert dst == k, "a moved table must land on the NEW shard"
            assert src != dst
        for tid in TABLES_1K:
            if tid not in moved:
                assert old.shard_of(tid) == new.shard_of(tid)

    def test_shrink_rehomes_only_top_shard(self):
        big, small = ShardMap(4), ShardMap(3)
        for tid in TABLES_1K:
            if big.shard_of(tid) == 3:
                assert small.shard_of(tid) in (0, 1, 2)
            else:
                assert small.shard_of(tid) == big.shard_of(tid)

    def test_partition_covers_exactly_once_including_empty(self):
        m = ShardMap(5)
        part = m.partition(TABLES_1K[:40])
        assert set(part) == set(range(5))  # empty shards listed too
        flat = [t for owned in part.values() for t in owned]
        assert sorted(flat) == TABLES_1K[:40]

    def test_balance_over_large_population(self):
        part = ShardMap(4).partition(TABLES_1K)
        sizes = [len(v) for v in part.values()]
        assert min(sizes) > 150, sizes  # ~250 ideal; gross skew = bug

    def test_validation(self):
        with pytest.raises(EtlError):
            ShardMap(0)
        with pytest.raises(EtlError):
            ShardMap(2, epoch=-1)
        with pytest.raises(EtlError):
            ShardMap(1).shrunk()
        assert ShardMap(2, epoch=3).grown() == ShardMap(3, epoch=4)


# ---------------------------------------------------------------------------
# slot naming (satellite: right-anchored parsing + round-trip properties)
# ---------------------------------------------------------------------------


class TestSlotNames:
    def test_round_trip_every_shape(self):
        """Property: every name the two builders can produce parses back
        to exactly the ids that built it — all four shapes (apply /
        table_sync × unsharded / sharded) across a spread of ids."""
        pids = [0, 1, 7, 123456]
        tids = [1, 16384, 999999999]
        shards = [None, 0, 3, 41]
        for pid in pids:
            for shard in shards:
                name = apply_slot_name(pid, shard)
                assert parse_slot_name(name) == ParsedSlot(pid, None, shard)
                for tid in tids:
                    n2 = table_sync_slot_name(pid, tid, shard)
                    assert parse_slot_name(n2) == ParsedSlot(pid, tid, shard)

    def test_shard_suffix_shapes(self):
        assert apply_slot_name(9, 2) == "supabase_etl_apply_9_s2"
        assert table_sync_slot_name(9, 16384, 2) == \
            "supabase_etl_table_sync_9_16384_s2"
        # unsharded names are byte-identical to the pre-sharding scheme
        assert apply_slot_name(9) == "supabase_etl_apply_9"
        assert table_sync_slot_name(9, 16384) == \
            "supabase_etl_table_sync_9_16384"

    def test_malformed_names_rejected_not_misparsed(self):
        for name in (
            "supabase_etl_apply_",            # no id
            "supabase_etl_apply_x",           # non-numeric id
            "supabase_etl_apply_1_s",         # shard marker, no digits
            "supabase_etl_apply_1_sX",        # shard marker, non-numeric
            "supabase_etl_apply_1_2_s3",      # extra field
            "supabase_etl_apply_+1",          # int() would accept this
            "supabase_etl_apply_1 ",          # trailing junk
            "supabase_etl_table_sync_1",      # missing table id
            "supabase_etl_table_sync_1_2_3",  # extra underscore field
            "supabase_etl_table_sync_1_2_3_s4",
            "supabase_etl_table_sync_a_2",
            "supabase_etl_table_sync_1_b",
            "someone_elses_slot",
        ):
            assert parse_slot_name(name) is None, name

    def test_cleanup_sweep_filters_by_shard(self):
        names = [apply_slot_name(1), apply_slot_name(1, 0),
                 apply_slot_name(1, 1), table_sync_slot_name(1, 5, 1),
                 apply_slot_name(2, 0), "foreign"]
        assert slots_for_pipeline(names, 1) == names[:4]
        assert slots_for_pipeline(names, 1, shard=1) == \
            [apply_slot_name(1, 1), table_sync_slot_name(1, 5, 1)]

    def test_length_bound_still_enforced(self):
        with pytest.raises(EtlError) as e:
            table_sync_slot_name(10**40, 10**15, 99)
        assert e.value.kind is ErrorKind.SLOT_NAME_TOO_LONG

    def test_negative_shard_rejected(self):
        with pytest.raises(EtlError):
            apply_slot_name(1, -1)


# ---------------------------------------------------------------------------
# store surface
# ---------------------------------------------------------------------------


class TestShardAssignmentStore:
    def test_json_round_trip(self):
        a = ShardAssignment(epoch=3, shard_count=4, status="rebalancing",
                            fence_lsn=777, next_shard_count=5,
                            moved=((16384, 0, 4), (16390, 2, 4)))
        assert ShardAssignment.from_json(a.to_json()) == a

    async def test_memory_store_persists_and_fences_epoch(self):
        from etl_tpu.store import MemoryStore

        s = MemoryStore()
        assert await s.get_shard_assignment() is None
        await s.update_shard_assignment(ShardAssignment(2, 2))
        await s.update_shard_assignment(ShardAssignment(3, 3))
        with pytest.raises(EtlError) as e:
            await s.update_shard_assignment(ShardAssignment(1, 2))
        assert e.value.kind is ErrorKind.PROGRESS_REGRESSION
        assert (await s.get_shard_assignment()).epoch == 3

    async def test_sqlite_store_survives_reconnect(self, tmp_path):
        from etl_tpu.store import SqliteStore

        path = tmp_path / "store.db"
        s = SqliteStore(path, 7)
        await s.connect()
        a = ShardAssignment(epoch=1, shard_count=3, status="steady")
        await s.update_shard_assignment(a)
        await s.close()
        s2 = SqliteStore(path, 7)
        await s2.connect()
        assert await s2.get_shard_assignment() == a
        # epoch fence also holds through the reloaded cache
        with pytest.raises(EtlError):
            await s2.update_shard_assignment(ShardAssignment(0, 2))
        await s2.close()

    async def test_sqlite_assignment_reads_through_not_cached(
            self, tmp_path):
        """The assignment is the one row another PROCESS (the
        coordinator) rewrites underneath a running pod: a pod's handle
        must observe the flip WITHOUT reconnecting, or the epoch fence
        could never refuse a stale pod in a real deployment."""
        from etl_tpu.store import SqliteStore

        path = tmp_path / "store.db"
        pod = SqliteStore(path, 1)
        await pod.connect()
        await pod.update_shard_assignment(ShardAssignment(0, 2))
        coordinator = SqliteStore(path, 1)  # a second handle = process
        await coordinator.connect()
        await coordinator.update_shard_assignment(ShardAssignment(1, 3))
        assert (await pod.get_shard_assignment()).epoch == 1
        await pod.close()
        await coordinator.close()

    async def test_sqlite_store_scoped_per_pipeline(self, tmp_path):
        from etl_tpu.store import SqliteStore

        path = tmp_path / "store.db"
        s1, s2 = SqliteStore(path, 1), SqliteStore(path, 2)
        await s1.connect()
        await s2.connect()
        await s1.update_shard_assignment(ShardAssignment(5, 4))
        assert await s2.get_shard_assignment() is None
        await s1.close()
        await s2.close()

    async def test_default_surface_for_plain_stores(self):
        """Stores that never shard keep working: reads say None, writes
        fail typed (never silently dropped)."""
        from etl_tpu.store.base import StateStore

        class Plain(StateStore):
            async def get_table_states(self): return {}
            async def get_table_state(self, t): return None
            async def update_table_state(self, t, s): pass
            async def delete_table_state(self, t): pass
            async def get_durable_progress(self, k): return None
            async def update_durable_progress(self, k, l): return True
            async def delete_durable_progress(self, k): pass
            async def get_destination_metadata(self, t): return None
            async def update_destination_metadata(self, m): pass
            async def delete_destination_metadata(self, t): pass

        p = Plain()
        assert await p.get_shard_assignment() is None
        with pytest.raises(EtlError):
            await p.update_shard_assignment(ShardAssignment(0, 2))


# ---------------------------------------------------------------------------
# shard-scoped store view
# ---------------------------------------------------------------------------


def _identity(shard=0, count=2, epoch=0):
    return ShardIdentity(pipeline_id=1, shard=shard, shard_count=count,
                         epoch=epoch)


class TestShardScopedStore:
    async def _store_with_tables(self, tables):
        from etl_tpu.models.table_state import TableState
        from etl_tpu.store import MemoryStore

        inner = MemoryStore()
        await inner.update_shard_assignment(ShardAssignment(0, 2))
        for tid in tables:
            await inner.update_table_state(tid, TableState.ready())
        return inner

    async def test_reads_filtered_to_owned_slice(self):
        tables = list(range(16384, 16392))
        inner = await self._store_with_tables(tables)
        smap = ShardMap(2)
        view0 = ShardScopedStore(inner, _identity(0))
        view1 = ShardScopedStore(inner, _identity(1))
        got0 = set(await view0.get_table_states())
        got1 = set(await view1.owned_table_states())
        assert got0 == set(smap.tables_for_shard(tables, 0))
        assert got1 == set(smap.tables_for_shard(tables, 1))
        assert got0 | got1 == set(tables) and not (got0 & got1)
        # single-table lookups honor the same boundary
        foreign = next(iter(got1))
        assert await view0.get_table_state(foreign) is None
        assert await view1.get_table_state(foreign) is not None

    async def test_write_to_foreign_table_refused(self):
        from etl_tpu.models.table_state import TableState

        tables = list(range(16384, 16392))
        inner = await self._store_with_tables(tables)
        view0 = ShardScopedStore(inner, _identity(0))
        foreign = ShardMap(2).tables_for_shard(tables, 1)[0]
        with pytest.raises(EtlError) as e:
            await view0.update_table_state(foreign, TableState.init())
        assert e.value.kind is ErrorKind.SHARD_NOT_OWNED
        with pytest.raises(EtlError):
            await view0.delete_table_state(foreign)

    async def test_stale_epoch_refused_after_flip(self):
        """'refuses tables owned by another epoch': once the coordinator
        bumps the authoritative epoch, a pod still holding the old one
        cannot write ANY table state — the rebalance safety fence."""
        from etl_tpu.models.table_state import TableState

        tables = list(range(16384, 16392))
        inner = await self._store_with_tables(tables)
        view0 = ShardScopedStore(inner, _identity(0, epoch=0))
        owned = ShardMap(2).tables_for_shard(tables, 0)[0]
        await view0.update_table_state(owned, TableState.ready())  # fine
        await inner.update_shard_assignment(
            ShardAssignment(epoch=1, shard_count=3))
        with pytest.raises(EtlError) as e:
            await view0.update_table_state(owned, TableState.ready())
        assert e.value.kind is ErrorKind.SHARD_EPOCH_STALE

    async def test_schema_ops_pass_through_but_cleanup_is_scoped(self):
        from etl_tpu.models import (ColumnSchema, Oid,
                                    ReplicatedTableSchema, TableName,
                                    TableSchema)

        tables = list(range(16384, 16392))
        inner = await self._store_with_tables(tables)
        view0 = ShardScopedStore(inner, _identity(0))
        foreign = ShardMap(2).tables_for_shard(tables, 1)[0]
        schema = ReplicatedTableSchema.with_all_columns(TableSchema(
            foreign, TableName("public", "x"),
            (ColumnSchema("id", Oid.INT8, nullable=False,
                          primary_key_ordinal=1),)))
        # the apply loop stores DDL versions for every table on the wire
        await view0.store_table_schema(schema, 5)
        assert await view0.get_table_schema(foreign) is not None
        # but the cleanup sweep only iterates OWNED tables
        assert foreign not in await view0.get_table_ids_with_schemas()

    async def test_pod_cannot_rewrite_assignment(self):
        inner = await self._store_with_tables([16384])
        view = ShardScopedStore(inner, _identity(0))
        with pytest.raises(EtlError):
            await view.update_shard_assignment(ShardAssignment(9, 9))

    async def test_resolve_shard_scope_bootstrap_and_mismatch(self):
        from etl_tpu.config import PipelineConfig
        from etl_tpu.sharding.runtime import resolve_shard_scope
        from etl_tpu.store import MemoryStore

        store = MemoryStore()
        cfg = PipelineConfig(pipeline_id=1, publication_name="pub",
                             shard=0, shard_count=2)
        scoped = await resolve_shard_scope(store, cfg)
        assert scoped.identity == _identity(0, 2, 0)
        assert (await store.get_shard_assignment()).shard_count == 2
        # a pod rolled with a stale K is refused
        bad = PipelineConfig(pipeline_id=1, publication_name="pub",
                             shard=0, shard_count=3)
        with pytest.raises(EtlError) as e:
            await resolve_shard_scope(store, bad)
        assert e.value.kind is ErrorKind.SHARD_EPOCH_STALE

    def test_config_validation(self):
        from etl_tpu.config import PipelineConfig

        with pytest.raises(EtlError):
            PipelineConfig(pipeline_id=1, publication_name="p",
                           shard=2, shard_count=2).validate()
        with pytest.raises(EtlError):
            PipelineConfig(pipeline_id=1, publication_name="p",
                           shard=None, shard_count=2).validate()
        PipelineConfig(pipeline_id=1, publication_name="p",
                       shard=1, shard_count=2).validate()


# ---------------------------------------------------------------------------
# sharded pipelines over one fake source
# ---------------------------------------------------------------------------


def _shard_cfg(shard, count, pipeline_id=1):
    from etl_tpu.config import (BatchConfig, BatchEngine, PipelineConfig,
                                SupervisionConfig)

    return PipelineConfig(
        pipeline_id=pipeline_id, publication_name="pub",
        batch=BatchConfig(max_size_bytes=64 * 1024, max_fill_ms=25,
                          batch_engine=BatchEngine("tpu")),
        supervision=SupervisionConfig(check_interval_s=0.25,
                                      stall_deadline_s=10.0,
                                      hang_deadline_s=25.0),
        wal_sender_timeout_ms=60_000, lag_sample_interval_s=0,
        shard=shard, shard_count=count)


class TestShardedPipelines:
    async def test_two_shards_split_one_publication(self):
        """K=2 shard pipelines over ONE fake database + shared store:
        each delivers exactly its slice, neither purges the other's
        tables at init, and the union covers the committed truth."""
        from etl_tpu.chaos.invariants import view_matches
        from etl_tpu.chaos.runner import (RecordingStore,
                                          TracingDestination, _Workload,
                                          _wait_until)
        from etl_tpu.chaos.scenario import Scenario
        from etl_tpu.models.event import (DeleteEvent, InsertEvent,
                                          UpdateEvent)
        from etl_tpu.models.table_state import TableStateType
        from etl_tpu.postgres.fake import FakeSource
        from etl_tpu.runtime import Pipeline

        shape = Scenario(name="s", description="d", tables=8,
                         rows_per_table=3, txs=4, rows_per_tx=20)
        wl = _Workload(shape, random.Random(7))
        db = wl.build_db()
        store = RecordingStore()
        part = ShardMap(2).partition(wl.table_ids)
        dests = {s: TracingDestination() for s in range(2)}
        pipes = {}
        try:
            for shard in range(2):
                pipes[shard] = Pipeline(
                    config=_shard_cfg(shard, 2), store=store,
                    destination=dests[shard],
                    source_factory=lambda: FakeSource(db))
                await pipes[shard].start()
            await _wait_until(
                lambda: all((st := store._states.get(tid)) is not None
                            and st.type is TableStateType.READY
                            for tid in wl.table_ids),
                30.0, "tables never ready")
            while wl.tx_index < shape.txs:
                await wl.run_tx(db)
            for shard in range(2):
                owned = part[shard]
                exp = {t: wl.expected[t] for t in owned}
                await _wait_until(
                    lambda sh=shard, o=owned, e=exp:
                        view_matches(dests[sh], o, e),
                    30.0, f"shard {shard} never delivered its slice")
                for e in dests[shard].events:
                    if isinstance(e, (InsertEvent, UpdateEvent,
                                      DeleteEvent)):
                        assert e.schema.id in owned, \
                            f"shard {shard} leaked table {e.schema.id}"
            # the shared store still knows EVERY table (no cross-purge)
            assert set(store._states) == set(wl.table_ids)
        finally:
            for p in pipes.values():
                if p._apply_task is not None:
                    await p.shutdown_and_wait()

    async def test_health_surfaces_shard_identity(self):
        from etl_tpu.destinations import MemoryDestination
        from etl_tpu.postgres.fake import FakeDatabase, FakeSource
        from etl_tpu.runtime import Pipeline
        from etl_tpu.store import MemoryStore

        db = FakeDatabase()
        p = Pipeline(config=_shard_cfg(1, 2), store=MemoryStore(),
                     destination=MemoryDestination(),
                     source_factory=lambda: FakeSource(db))
        snap = p.health_snapshot()
        assert snap["shard"] == {"shard": 1, "shard_count": 2,
                                 "epoch": None}  # not adopted yet

        from etl_tpu.replicator import build_observability_app
        app = build_observability_app(p)
        assert app is not None  # route construction with a sharded pod


# ---------------------------------------------------------------------------
# two-phase rebalance
# ---------------------------------------------------------------------------


class TestRebalance:
    async def test_add_shard_fence_handoff_loses_nothing(self):
        """The acceptance bar: K=2→3 mid-stream. The coordinator fences
        at the new slot's consistent point, waits for the losing shards
        to drain to the fence, flips the epoch; the rolled fleet (K=3)
        finishes the workload and the UNION of all destinations equals
        the committed source truth — zero loss across the handoff."""
        from etl_tpu.chaos.invariants import view_matches
        from etl_tpu.chaos.runner import (RecordingStore,
                                          TracingDestination, _Workload,
                                          _wait_until)
        from etl_tpu.chaos.scenario import Scenario
        from etl_tpu.chaos.sharded import _UnionDest
        from etl_tpu.models.table_state import TableStateType
        from etl_tpu.postgres.fake import FakeSource
        from etl_tpu.runtime import Pipeline
        from etl_tpu.sharding import ShardCoordinator

        shape = Scenario(name="s", description="d", tables=8,
                         rows_per_table=3, txs=10, rows_per_tx=30)
        wl = _Workload(shape, random.Random(11))
        db = wl.build_db()
        store = RecordingStore()
        dests = {s: TracingDestination() for s in range(3)}
        pipes = []

        async def start_fleet(k):
            fleet = []
            for shard in range(k):
                p = Pipeline(config=_shard_cfg(shard, k), store=store,
                             destination=dests[shard],
                             source_factory=lambda: FakeSource(db))
                await p.start()
                fleet.append(p)
            return fleet

        try:
            pipes = await start_fleet(2)
            await _wait_until(
                lambda: all((st := store._states.get(tid)) is not None
                            and st.type is TableStateType.READY
                            for tid in wl.table_ids),
                30.0, "never ready")
            while wl.tx_index < 5:
                await wl.run_tx(db)

            coord = ShardCoordinator(store, 1, lambda: FakeSource(db),
                                     quiesce_timeout_s=30.0)
            rebalance = asyncio.ensure_future(coord.add_shard())
            # traffic keeps flowing THROUGH the rebalance — durable
            # progress crosses the fence because the old owners keep
            # applying, not because the world stopped
            for _ in range(3):
                await asyncio.sleep(0.15)
                await wl.run_tx(db)
            result = await rebalance
            assert result.new_shard_count == 3
            assert result.new_epoch == result.old_epoch + 1
            assert result.moved, "growing K must re-home some tables"
            for tid, (src, dst) in result.moved.items():
                assert dst == 2

            assignment = await store.get_shard_assignment()
            assert assignment == ShardAssignment(epoch=1, shard_count=3)

            # roll the fleet (stale pods would now be refused by the
            # epoch fence) and finish the workload at K=3
            for p in pipes:
                await p.shutdown_and_wait()
            pipes = await start_fleet(3)
            while wl.tx_index < shape.txs:
                await wl.run_tx(db)
            await _wait_until(
                lambda: view_matches(_UnionDest(list(dests.values())),
                                     wl.table_ids, wl.expected),
                30.0, "union never converged after the rebalance")
        finally:
            for p in pipes:
                if p._apply_task is not None:
                    await p.shutdown_and_wait()

    async def test_conflicting_rebalance_refused(self):
        """An in-flight record targeting a DIFFERENT transition refuses;
        the SAME transition resumes (crash/timeout retry) instead of
        bricking the coordinator."""
        from etl_tpu.postgres.fake import FakeDatabase, FakeSource
        from etl_tpu.sharding import (STATUS_REBALANCING,
                                      ShardCoordinator)
        from etl_tpu.store import MemoryStore

        store = MemoryStore()
        await store.update_shard_assignment(ShardAssignment(
            epoch=0, shard_count=2, status=STATUS_REBALANCING,
            fence_lsn=100, next_shard_count=3))
        coord = ShardCoordinator(store, 1,
                                 lambda: FakeSource(FakeDatabase()))
        # an add (next=3) is in flight → a remove (next=1) must refuse
        with pytest.raises(EtlError) as e:
            await coord.remove_shard()
        assert e.value.kind is ErrorKind.INVALID_STATE_TRANSITION

    async def test_resume_after_timeout_completes(self):
        """A quiesce timeout leaves the rebalancing record; once the
        slow shard drains past the persisted fence, re-running the SAME
        action completes the flip with the SAME fence."""
        from etl_tpu.models.lsn import Lsn
        from etl_tpu.models.table_state import TableState
        from etl_tpu.postgres.fake import FakeDatabase, FakeSource
        from etl_tpu.sharding import ShardCoordinator
        from etl_tpu.store import MemoryStore

        db = FakeDatabase()
        store = MemoryStore()
        await store.update_shard_assignment(ShardAssignment(0, 2))
        moving = next(iter(moved_tables(ShardMap(2), ShardMap(3),
                                        TABLES_1K)))
        await store.update_table_state(moving, TableState.ready())
        coord = ShardCoordinator(store, 1, lambda: FakeSource(db),
                                 quiesce_timeout_s=0.2,
                                 poll_interval_s=0.02)
        with pytest.raises(EtlError):
            await coord.add_shard()  # no pipeline → quiesce times out
        fence = (await store.get_shard_assignment()).fence_lsn
        losing = ShardMap(2).shard_of(moving)
        await store.update_durable_progress(
            apply_slot_name(1, losing), Lsn(fence + 1))
        result = await coord.add_shard()  # resume, not refuse
        assert result.fence_lsn == fence
        assert (await store.get_shard_assignment()) == \
            ShardAssignment(epoch=1, shard_count=3)

    async def test_abort_rebalance_rolls_back_and_frees_slot(self):
        from etl_tpu.models.table_state import TableState
        from etl_tpu.postgres.fake import FakeDatabase, FakeSource
        from etl_tpu.sharding import ShardCoordinator
        from etl_tpu.store import MemoryStore

        db = FakeDatabase()
        store = MemoryStore()
        await store.update_shard_assignment(ShardAssignment(0, 2))
        moving = next(iter(moved_tables(ShardMap(2), ShardMap(3),
                                        TABLES_1K)))
        await store.update_table_state(moving, TableState.ready())
        coord = ShardCoordinator(store, 1, lambda: FakeSource(db),
                                 quiesce_timeout_s=0.2,
                                 poll_interval_s=0.02)
        with pytest.raises(EtlError):
            await coord.add_shard()
        assert apply_slot_name(1, 2) in db.slots  # fence slot created
        await coord.abort_rebalance()
        assert (await store.get_shard_assignment()) == \
            ShardAssignment(epoch=0, shard_count=2)
        assert apply_slot_name(1, 2) not in db.slots  # cannot pin WAL
        await coord.abort_rebalance()  # idempotent no-op when steady

    async def test_quiesce_timeout_is_typed(self):
        """A shard that never drains to the fence fails the rebalance
        loudly with TIMEOUT (the in-flight record stays for a retry)."""
        from etl_tpu.models.table_state import TableState
        from etl_tpu.postgres.fake import FakeDatabase, FakeSource
        from etl_tpu.sharding import ShardCoordinator
        from etl_tpu.store import MemoryStore

        db = FakeDatabase()
        store = MemoryStore()
        await store.update_shard_assignment(ShardAssignment(0, 2))
        # seed a table that actually MOVES at K=2→3, so the quiesce wait
        # has a losing shard to wait for
        moving = next(iter(moved_tables(ShardMap(2), ShardMap(3),
                                        TABLES_1K)))
        await store.update_table_state(moving, TableState.ready())
        # no pipelines running → durable progress never reaches any fence
        coord = ShardCoordinator(store, 1, lambda: FakeSource(db),
                                 quiesce_timeout_s=0.3,
                                 poll_interval_s=0.02)
        with pytest.raises(EtlError) as e:
            await coord.add_shard()
        assert e.value.kind is ErrorKind.TIMEOUT
        assignment = await store.get_shard_assignment()
        assert assignment.rebalancing and assignment.next_shard_count == 3


# ---------------------------------------------------------------------------
# sharded chaos (the pod-kill scenario, also smoke-gated)
# ---------------------------------------------------------------------------


class TestShardedChaos:
    async def test_pod_kill_scenario_passes(self):
        from etl_tpu.chaos.sharded import run_sharded_scenario

        run = await run_sharded_scenario(seed=7)
        assert run.ok, run.describe()
        assert run.union_matches
        assert run.survivor_txs_during_outage > 0
        assert len(run.restarts) == 1
        assert all(n > 0 for n in run.tables_per_shard.values())

    async def test_deterministic_per_seed(self):
        from etl_tpu.chaos.sharded import run_sharded_scenario

        a = (await run_sharded_scenario(seed=23)).describe()
        b = (await run_sharded_scenario(seed=23)).describe()
        for d in (a, b):
            d.pop("duration_s")
            for r in d["restarts"]:
                r.pop("recovery_s")
        assert a == b


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------


class TestShardedOrchestration:
    async def test_k8s_fan_out_creates_one_replica_set_per_shard(self):
        from etl_tpu.api.orchestrator import (K8sOrchestrator,
                                              ReplicatorSpec)
        from etl_tpu.testing.fake_http import RecordingHttpServer

        server = RecordingHttpServer()
        await server.start()
        try:
            orch = K8sOrchestrator(api_url=server.url(), namespace="etl")
            spec = ReplicatorSpec(
                pipeline_id=7, tenant_id="acme",
                config={"pipeline_id": 7, "publication_name": "pub",
                        "shard_count": 2})
            await orch.start_pipeline(spec)
            sts = [r.json for r in server.requests
                   if r.path.endswith("/statefulsets")
                   and r.method == "POST"]
            names = [s["metadata"]["name"] for s in sts]
            assert names == ["etl-replicator-7-s0", "etl-replicator-7-s1"]
            for i, s in enumerate(sts):
                assert s["metadata"]["labels"]["shard"] == str(i)
            # each pod's ConfigMap carries its OWN shard identity
            cms = [r.json for r in server.requests
                   if r.path.endswith("/configmaps")]
            for i, cm in enumerate(cms):
                assert f"shard: {i}" in cm["data"]["base.yaml"]
                assert "shard_count: 2" in cm["data"]["base.yaml"]
            await orch.shutdown()
        finally:
            await server.stop()

    async def test_k8s_stop_sweeps_discovered_shards(self):
        from etl_tpu.api.orchestrator import K8sOrchestrator
        from etl_tpu.testing.fake_http import RecordingHttpServer

        server = RecordingHttpServer()
        await server.start()
        try:
            # the fake returns {} by default; script real-looking
            # statefulset docs for shards 0 and 1 so discovery finds
            # exactly two replica sets
            def responder(req):
                if req.method == "GET" and "statefulsets" in req.path:
                    for s in (0, 1):
                        if req.path.endswith(f"etl-replicator-3-s{s}"):
                            return 200, {"metadata": {
                                "name": f"etl-replicator-3-s{s}"}}
                    return 404, {}
                return None

            server.responders.append(responder)
            orch = K8sOrchestrator(api_url=server.url(), namespace="etl")
            await orch.stop_pipeline(3)
            deletes = [p for p in server.paths() if p.startswith("DELETE")]
            for name in ("etl-replicator-3", "etl-replicator-3-s0",
                         "etl-replicator-3-s1"):
                assert f"DELETE /apis/apps/v1/namespaces/etl/" \
                       f"statefulsets/{name}" in deletes
            assert not any("-s2" in p for p in deletes)
            await orch.shutdown()
        finally:
            await server.stop()

    async def test_k8s_status_aggregates_worst_shard(self):
        from etl_tpu.api.orchestrator import K8sOrchestrator
        from etl_tpu.testing.fake_http import RecordingHttpServer

        server = RecordingHttpServer()
        await server.start()
        try:
            def responder(req):
                if req.method != "GET":
                    return None
                if "statefulsets" in req.path:
                    if req.path.endswith("-s0"):
                        return 200, {"metadata": {},
                                     "status": {"readyReplicas": 1}}
                    if req.path.endswith("-s1"):
                        return 200, {"metadata": {},
                                     "status": {"readyReplicas": 0}}
                    return 404, {}
                if "/pods" in req.path:
                    return 200, {"items": []}
                return None

            server.responders.append(responder)
            orch = K8sOrchestrator(api_url=server.url(), namespace="etl")
            st = await orch.status(4)
            # one ready shard + one still coming up → starting, not
            # running: a hidden dead shard must never read as healthy
            assert st.state == "starting"
            assert "s0=running" in st.detail and "s1=" in st.detail
            await orch.shutdown()
        finally:
            await server.stop()

    async def test_local_orchestrator_shards_and_reshards(
            self, tmp_path, monkeypatch):
        import asyncio as aio
        import sys

        import yaml

        from etl_tpu.api.orchestrator import (LocalOrchestrator,
                                              ReplicatorSpec)

        spawned = []
        real_exec = aio.create_subprocess_exec

        async def fake_exec(*args, **kwargs):
            spawned.append(args)
            return await real_exec(sys.executable, "-c",
                                   "import time; time.sleep(60)",
                                   **{k: v for k, v in kwargs.items()
                                      if k in ("stdout", "stderr")})

        monkeypatch.setattr(aio, "create_subprocess_exec", fake_exec)
        orch = LocalOrchestrator(str(tmp_path))
        spec = ReplicatorSpec(5, "t", {"publication_name": "p",
                                       "shard_count": 2})
        await orch.start_pipeline(spec)
        assert set(orch._procs) == {(5, 0), (5, 1)}
        assert (await orch.status(5)).state == "running"
        for shard in range(2):
            conf = yaml.safe_load(
                (tmp_path / f"pipeline-5-s{shard}" / "base.yaml")
                .read_text())
            assert conf["shard"] == shard and conf["shard_count"] == 2
        # reshard 2→3: the old fleet keys are reused/extended
        spec3 = ReplicatorSpec(5, "t", {"publication_name": "p",
                                        "shard_count": 3})
        await orch.start_pipeline(spec3)
        assert set(orch._procs) == {(5, 0), (5, 1), (5, 2)}
        await orch.stop_pipeline(5)
        assert not orch._procs
        assert (await orch.status(5)).state == "stopped"
        await orch.shutdown()
