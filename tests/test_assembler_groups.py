"""The assembler's open group (ISSUE 34): one open run per table within a
flush, sealed together, and the delivery order that has to survive it.

- every `flush_bounded` cut covers one contiguous stretch of WAL, at
  every `max_bytes`: no row stays behind a row with higher coordinates,
  `covered` names a commit all of whose rows have left, and a table's
  rows leave in push order;
- the same interleaved stream through the whole pipeline into the
  transactional memory sink, at `write_window` 1 and 4, lands every row
  once and ends in the state the CPU engine ends in;
- what seals the group: a run reaching `seal_rows`, the group's bytes
  reaching `seal_bytes`, a control event, a changed schema object.
"""

from __future__ import annotations

import asyncio
import random

import pytest

from etl_tpu.config.pipeline import BatchEngine
from etl_tpu.models import (ColumnSchema, Oid, ReplicatedTableSchema,
                            TableName, TableSchema)
from etl_tpu.models.event import (DecodedBatchEvent, DeleteEvent,
                                  InsertEvent, RelationEvent, UpdateEvent)
from etl_tpu.models.lsn import Lsn
from etl_tpu.postgres.codec import pgoutput
from etl_tpu.runtime.assembler import EventAssembler

TIDS = {"a": 16401, "b": 16402, "c": 16403}


def _table(tid: int, name: str) -> TableSchema:
    return TableSchema(
        tid, TableName("public", name),
        (ColumnSchema("id", Oid.INT4, nullable=False, primary_key_ordinal=1),
         ColumnSchema("v", Oid.INT4),
         ColumnSchema("note", Oid.TEXT)))


def _schemas() -> dict:
    return {k: ReplicatedTableSchema.with_all_columns(_table(tid, f"grp_{k}"))
            for k, tid in TIDS.items()}


def _payload(table: str, i: int) -> bytes:
    # widths differ by table and by row, so events differ in size
    return pgoutput.encode_insert(
        TIDS[table], [str(i).encode(), str(i * 7).encode(),
                      b"x" * (1 + (i * 5 + len(table)) % 23)])


def _coords(ev: DecodedBatchEvent) -> list:
    return [(int(lsn), int(o)) for lsn, o in zip(ev.commit_lsns,
                                                 ev.tx_ordinals)]


# ---------------------------------------------------------------------------
# (a) every cut is a contiguous stretch of WAL
# ---------------------------------------------------------------------------


def _interleaved_stream(seed: int = 34) -> list:
    """("row", table, commit_lsn, ordinal) | ("commit", commit_lsn,
    end_lsn) | ("control", table): eight transactions of 2–7 rows over
    three tables in random interleaving, a Relation inside the fifth."""
    rng = random.Random(seed)
    ops = []
    for t in range(8):
        commit_lsn = 1000 + 100 * t
        for o in range(rng.randint(2, 7)):
            if t == 4 and o == 1:
                ops.append(("control", "b"))
            ops.append(("row", rng.choice("aabbc"), commit_lsn, o))
        ops.append(("commit", commit_lsn, commit_lsn + 50))
    return ops


def _push_stream(a: EventAssembler, schemas: dict, ops: list) -> None:
    n = 0
    for op in ops:
        if op[0] == "row":
            _, table, commit_lsn, ordinal = op
            n += 1
            a.push_raw_row(_payload(table, n), schemas[table],
                           Lsn(commit_lsn - 90 + ordinal), Lsn(commit_lsn),
                           ordinal)
        elif op[0] == "commit":
            a.note_commit_end(Lsn(op[2]))
        else:
            a.push_control(RelationEvent(Lsn(1), Lsn(1), schemas[op[1]]))


def _drain_in_cuts(ops: list, max_bytes: int) -> list:
    """[(events, covered, remaining)] of one assembler fed `ops` and
    flushed at `max_bytes` until empty."""
    schemas = _schemas()
    a = EventAssembler(BatchEngine.TPU)
    a.seal_rows = 4  # groups seal by size inside transactions too
    try:
        _push_stream(a, schemas, ops)
        cuts = []
        while len(a):
            cuts.append(a.flush_bounded(max_bytes=max_bytes))
            assert cuts[-1][0], "a cut holds at least one event"
        return cuts
    finally:
        a.close()


def _check_cuts(ops: list, cuts: list) -> None:
    pushed = [(op[1], (op[2], op[3])) for op in ops if op[0] == "row"]
    ends = {op[1]: op[2] for op in ops if op[0] == "commit"}
    marks = sorted(ends.values())
    delivered: list = []  # (table id, coordinates), in delivery order
    highest = (0, 0)
    last_covered = 0
    for events, covered, remaining in cuts:
        here = [(ev.schema.id, c) for ev in events
                if isinstance(ev, DecodedBatchEvent) for c in _coords(ev)]
        if here:
            # one contiguous stretch of WAL: everything in this cut lies
            # above everything that left before it — so no row stayed
            # behind a row with higher coordinates
            assert min(c for _, c in here) > highest
            highest = max(c for _, c in here)
        delivered += here
        if covered is not None:
            assert int(covered) in marks and int(covered) >= last_covered
            last_covered = int(covered)
            gone = {c for _, c in delivered}
            for _, (commit_lsn, ordinal) in pushed:
                if ends[commit_lsn] <= int(covered):
                    assert (commit_lsn, ordinal) in gone, \
                        f"covered {covered} claims an undelivered row"
        pending = [m for m in marks if m > last_covered]
        assert (int(remaining) if remaining is not None else None) \
            == (pending[-1] if pending else None)
    assert last_covered == marks[-1]
    for table, tid in TIDS.items():
        assert [c for t, c in delivered if t == tid] \
            == [c for t, c in pushed if t == table], \
            f"table {table}: rows left out of push order"
    assert len(delivered) == len(pushed)


def _window_bytes(ops: list) -> int:
    rows = [op for op in ops if op[0] == "row"]
    return 64 * sum(op[0] != "commit" for op in ops) + sum(
        len(_payload(op[1], i + 1)) for i, op in enumerate(rows))


def test_the_finest_cut_is_one_group_a_flush():
    ops = _interleaved_stream()
    whole = _drain_in_cuts(ops, 1 << 30)
    assert len(whole) == 1
    _check_cuts(ops, whole)
    finest = _drain_in_cuts(ops, 64)
    _check_cuts(ops, finest)
    # some group holds several tables' runs whose coordinates interleave:
    # the case a cut inside a group would break
    assert any(
        len(batches) >= 2
        and min(_coords(batches[1])) < max(_coords(batches[0]))
        for batches in ([e for e in events
                         if isinstance(e, DecodedBatchEvent)]
                        for events, _, _ in finest))
    assert sum(len(events) for events, _, _ in finest) > len(finest) > 4


@pytest.mark.parametrize("quarter", range(4))
def test_every_cut_is_a_contiguous_stretch_of_wal(quarter):
    """Every `max_bytes` from one event's size (a control event's 64
    bytes) to the whole window, a quarter of the range a case."""
    ops = _interleaved_stream()
    total = _window_bytes(ops)
    lo = 64 + (total - 64) * quarter // 4
    hi = 64 + (total - 64) * (quarter + 1) // 4
    for max_bytes in range(lo, hi + 1):
        _check_cuts(ops, _drain_in_cuts(ops, max_bytes))


# ---------------------------------------------------------------------------
# (b) the whole pipeline into the transactional sink
# ---------------------------------------------------------------------------


async def _stream_three_tables(engine: BatchEngine, write_window: int):
    """Commit a backlog of transactions that interleave inserts, updates
    and deletes over three tables, drain it through `write_window` into
    a transactional memory sink behind 3 ms-late acks; the sink."""
    from etl_tpu.config import BatchConfig, PipelineConfig
    from etl_tpu.destinations import (DelayedAckDestination,
                                      TransactionalMemoryDestination)
    from etl_tpu.models.table_state import TableStateType
    from etl_tpu.postgres.fake import FakeDatabase, FakeSource
    from etl_tpu.runtime import Pipeline
    from etl_tpu.store import NotifyingStore

    db = FakeDatabase()
    for k, tid in TIDS.items():
        db.create_table(_table(tid, f"grp_{k}"))
    db.create_publication("pub", list(TIDS.values()))
    store = NotifyingStore()
    sink = TransactionalMemoryDestination()
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_size_bytes=700, max_fill_ms=10,
                              batch_engine=engine,
                              write_window=write_window)),
        store=store, destination=DelayedAckDestination(sink, 0.003),
        source_factory=lambda: FakeSource(db))
    await pipeline.start()
    for tid in TIDS.values():
        await asyncio.wait_for(
            store.notify_on(tid, TableStateType.READY), 60)
    rng = random.Random(3434)
    live = {tid: [] for tid in TIDS.values()}
    n_ops = 0
    next_id = 1
    for _ in range(40):
        tx = db.transaction()
        for _ in range(rng.randint(3, 9)):
            tid = rng.choice(list(TIDS.values()))
            kind = rng.random()
            if kind < 0.55 or not live[tid]:
                tx.insert(tid, [str(next_id), str(next_id % 97), "n" * (
                    1 + next_id % 11)])
                live[tid].append(next_id)
                next_id += 1
            elif kind < 0.85:
                key = rng.choice(live[tid])
                tx.update(tid, [str(key), None, None],
                          [str(key), str(rng.randint(0, 999)), "u"])
            else:
                key = live[tid].pop(rng.randrange(len(live[tid])))
                tx.delete(tid, [str(key), None, None])
            n_ops += 1
        await tx.commit()

    def rows():
        return [e for e in sink.events
                if isinstance(e, (InsertEvent, UpdateEvent, DeleteEvent))]

    while len(rows()) < n_ops:
        assert not pipeline._apply_task.done(), "pipeline stopped early"
        assert sink.dedup_skipped_rows == 0, \
            "a row arrived behind a row with higher coordinates"
        await asyncio.sleep(0.005)
    await pipeline.shutdown_and_wait()
    return sink, rows(), n_ops


def _end_state(rows: list) -> dict:
    state: dict = {tid: {} for tid in TIDS.values()}
    for e in rows:
        if isinstance(e, DeleteEvent):
            del state[e.schema.id][e.old_row.values[0]]
        else:
            state[e.schema.id][e.row.values[0]] = tuple(e.row.values)
    return state


@pytest.mark.parametrize("write_window", [1, 4])
async def test_transactional_sink_sees_every_row_once(write_window):
    sink, rows, n_ops = await _stream_three_tables(BatchEngine.TPU,
                                                   write_window)
    assert sink.dedup_skipped_rows == 0
    assert sink.uncoordinated_writes == 0
    coords = [(e.schema.id, int(e.commit_lsn), e.tx_ordinal) for e in rows]
    assert len(coords) == len(set(coords)) == n_ops
    # high water only ever rose, flush after flush
    assert sink.high_water_log == sorted(sink.high_water_log)
    _, cpu_rows, cpu_ops = await _stream_three_tables(BatchEngine.CPU,
                                                      write_window)
    assert cpu_ops == n_ops
    assert _end_state(rows) == _end_state(cpu_rows)
    # a table's rows arrive in WAL order on both engines
    for tid in TIDS.values():
        mine = [c[1:] for c in coords if c[0] == tid]
        assert mine == sorted(mine)
        assert mine == [(int(e.commit_lsn), e.tx_ordinal)
                        for e in cpu_rows if e.schema.id == tid]


# ---------------------------------------------------------------------------
# (c) (d) (e) what seals the group
# ---------------------------------------------------------------------------


def _push(a: EventAssembler, schemas: dict, table: str, i: int) -> None:
    a.push_raw_row(_payload(table, i), schemas[table], Lsn(100 + i),
                   Lsn(900), i)


def _bulk(a: EventAssembler, schemas: dict, table: str, first: int,
          k: int) -> None:
    a.push_raw_rows([_payload(table, first + j) for j in range(k)],
                    schemas[table], [100 + first + j for j in range(k)],
                    900, first)


def _sealed(a: EventAssembler) -> list:
    """(table, rows) of every event sealed so far."""
    names = {tid: k for k, tid in TIDS.items()}
    return [(names[e.schema.id], len(e.tx_ordinals)) for e in a._events]


def test_a_run_reaching_seal_rows_seals_the_whole_group():
    schemas = _schemas()
    a = EventAssembler(BatchEngine.TPU)
    a.seal_rows = 8
    try:
        for i, table in enumerate("aabab"):
            _push(a, schemas, table, i)
        assert _sealed(a) == [] and len(a) == 2  # two open runs
        _bulk(a, schemas, "a", 5, 5)  # 3 + 5 = seal_rows: seals a AND b
        assert _sealed(a) == [("a", 8), ("b", 2)]
        assert len(a) == 2  # nothing is left open
        for i, table in enumerate("aaaaaacb", start=10):
            _push(a, schemas, table, i)
        _bulk(a, schemas, "a", 18, 5)  # 6 + 5 > seal_rows: seal, then extend
        assert _sealed(a)[2:] == [("a", 6), ("c", 1), ("b", 1)]
        events = a.flush()
        assert [len(e.tx_ordinals) for e in events] == [8, 2, 6, 1, 1, 5]
        assert max(len(e.tx_ordinals) for e in events) <= a.seal_rows
    finally:
        a.close()


def test_group_bytes_reaching_seal_bytes_seal_the_group():
    schemas = _schemas()
    sizes = [64 + len(_payload(t, i)) for i, t in enumerate("abcabc")]
    # the bound falls on the sixth row; no single table's run reaches it
    a = EventAssembler(BatchEngine.TPU, seal_bytes=sum(sizes) - 1)
    try:
        for i, table in enumerate("abcab"):
            _push(a, schemas, table, i)
        assert _sealed(a) == []
        assert max(sizes[0] + sizes[3], sizes[1] + sizes[4],
                   sizes[2] + sizes[5]) < a.seal_bytes
        _push(a, schemas, "c", 5)
        assert _sealed(a) == [("a", 2), ("b", 2), ("c", 2)]
        assert len(a) == 3
        _push(a, schemas, "b", 6)  # a new group opens
        assert len(a) == 4 and a.size_bytes == sum(sizes) + 64 + len(
            _payload("b", 6))
    finally:
        a.close()


@pytest.mark.parametrize("relation_event", [True, False],
                         ids=["relation-event", "schema-object-alone"])
def test_a_changed_relation_seals_before_the_new_schema_s_rows(
        relation_event):
    schemas = _schemas()
    changed = ReplicatedTableSchema.with_all_columns(
        _table(TIDS["a"], "grp_a"))
    assert changed is not schemas["a"]
    a = EventAssembler(BatchEngine.TPU)
    try:
        for i, table in enumerate("abab"):
            _push(a, schemas, table, i)
        if relation_event:
            a.push_control(RelationEvent(Lsn(110), Lsn(900), changed))
            # a control event leaves no run open behind it
            assert len(a) == 3
        a.push_raw_row(_payload("a", 7), changed, Lsn(111), Lsn(900), 7)
        _push(a, schemas, "b", 8)
        events = a.flush()
    finally:
        a.close()
    batches = [e for e in events if isinstance(e, DecodedBatchEvent)]
    assert [(e.schema is changed, e.schema.id, list(map(int, e.tx_ordinals)))
            for e in batches] == [
        (False, TIDS["a"], [0, 2]), (False, TIDS["b"], [1, 3]),
        (True, TIDS["a"], [7]), (False, TIDS["b"], [8])]
    if relation_event:
        assert isinstance(events[2], RelationEvent) and len(events) == 5
    assert all(e.schema is schemas["a"] for e in batches[:1])


def test_a_multi_table_flush_is_one_columnar_write_a_table():
    """Thirty interleaved statements over three tables reach a columnar
    destination as three writes (one per table, first-row order), every
    row decoded to its own values under its own coordinates."""
    from etl_tpu.destinations.base import sequential_batch_program

    schemas = _schemas()
    a = EventAssembler(BatchEngine.TPU)
    try:
        order = "cab" * 10
        for i, table in enumerate(order):
            _push(a, schemas, table, i)
        events = a.flush()
        ops = list(sequential_batch_program(events))
    finally:
        a.close()
    assert [(op[0], op[1].id) for op in ops] == [
        ("batch", TIDS[t]) for t in "cab"]
    for (_, _, cb), table in zip(ops, "cab"):
        mine = [i for i, t in enumerate(order) if t == table]
        assert list(map(int, cb.tx_ordinals)) == mine
        assert [r.values[:2] for r in cb.batch.to_rows()] \
            == [[i, i * 7] for i in mine]
