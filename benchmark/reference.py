"""The plain reference: what the sink has to hold, worked out from the
deployment's generator alone.

Independent of the program: it imports nothing of `etl_tpu` and takes
nothing the program made. From the generator's snapshot and operation log
`L` and from what the sink holds `D` (every delivered row with its table,
its columns and NULLs, its change kind, its old or key image where the sink
keeps one, and the WAL coordinates `(commit_lsn, tx_ordinal)` the program
booked it to) it counts, all with the limit 0 because every comparison is
exact:

  missing_rows        an event of `L` whose commit the slot's reported flush
                      position has passed (a snapshot row, for a copy) that
                      is not in `D`: at-least-once delivery, broken
  unknown_rows        a row of `D` that was never sent: coordinates that are
                      no event's, or an event's the source had not sent yet
                      (a key outside the snapshot, for a copy)
  misattributed_rows  a row of `D` whose coordinates belong to an event of
                      another table or of another key
  wrong_rows          a row of `D` that differs from its event: the kind of
                      change, any column of the new image, the old or key
                      image, NULL where a value was sent or a value where
                      NULL was. Integers, dates and timestamps compare
                      exactly, text byte for byte, NUMERIC as decimals, a
                      float8 bit for bit. An unchanged-TOAST cell may arrive
                      marked unchanged or with the value it had
  state_mismatch_rows keys whose final state differs: replaying `D` in
                      delivery order over the snapshot (upsert by key,
                      delete by key, an update that moves a row deletes its
                      old key) has to leave every key as replaying `L`, as
                      far as that table was delivered, leaves it: present or
                      not, and last written by the same event. Redelivery in
                      order passes; an update dropped, two updates of a key
                      swapped, or a change under the wrong key fails

The guarantee the configurations state is at-least-once delivery in commit
order with durable progress; these five numbers are what a run can show of
it. Copy rows carry no coordinates and are matched by primary key.
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np

from oplog import (DELETE, INSERT, TEXT_TYPES, UPDATE, char_width,
                   key_indices, n_rows)

LIMITS = {"missing_rows": 0, "wrong_rows": 0, "unknown_rows": 0,
          "misattributed_rows": 0, "state_mismatch_rows": 0}
# how a sink labels a change: the three kinds, or only upsert against delete
GOT_INSERT, GOT_UPDATE, GOT_DELETE, GOT_UPSERT = 0, 1, 2, 3


def judge(numbers: dict) -> tuple:
    """(correct, [[name, number, limit], ...]) — each number beside its
    limit, in the order they are printed."""
    table = [[k, numbers[k], LIMITS[k]] for k in LIMITS if k in numbers]
    extra = [[k, v, 0] for k, v in numbers.items() if k not in LIMITS]
    table += extra
    return all(v <= lim for _, v, lim in table), table


# ---------------------------------------------------------------------------
# comparing one column
# ---------------------------------------------------------------------------


def _expected_text(column: dict, values, rows, n: int):
    """The text a sink has to hold: bpchar as the server pads it."""
    import pyarrow as pa

    width = char_width(column) if column["type"] == "bpchar" else None
    if isinstance(values, bytes):
        return values.decode().ljust(width or 0)
    if not n:
        return pa.array([], type=pa.string())
    picked = np.char.decode(values if rows is None else values[rows], "utf-8")
    return pa.array(np.char.ljust(picked, width) if width else picked,
                    type=pa.string())


def _differs(column: dict, got, expected, rows, n: int) -> np.ndarray:
    """bool[n]: where the sink's column `got` = (values, null, unchanged)
    differs from rows `rows` (None: all) of the generator's Col."""
    values, null, unchanged = got
    want_null = expected.null if rows is None or expected.null is None \
        else expected.null[rows]
    kind = column["type"]
    if kind in TEXT_TYPES:
        import pyarrow as pa
        import pyarrow.compute as pc

        have = values if isinstance(values, (pa.Array, pa.ChunkedArray)) \
            else pa.array(values, type=pa.string())
        ne = pc.not_equal(
            have, _expected_text(column, expected.values, rows, n))
        if ne.null_count:
            ne = pc.fill_null(ne, True)
        bad = ne.to_numpy(zero_copy_only=False).astype(bool, copy=False) \
            if n else np.zeros(0, dtype=bool)
    elif kind == "numeric":
        scale = int(column.get("scale", 0))
        want = expected.values if rows is None else expected.values[rows]
        bad = np.fromiter(
            (t is not None
             and Decimal(str(t)) != Decimal(w).scaleb(-scale)
             for t, w in zip(values, want.tolist())), dtype=bool, count=n)
    else:
        want = expected.values if rows is None else expected.values[rows]
        have = np.asarray(values)
        if kind == "float8":
            bad = (have != want) & ~(np.isnan(have) & np.isnan(want))
        elif have.dtype.kind == "u" or np.asarray(want).dtype.kind == "u":
            bad = have.astype(np.int64) != np.asarray(want).astype(np.int64)
        else:
            bad = have != want
    if null is not None or want_null is not None:
        zeros = np.zeros(n, dtype=bool)
        null = zeros if null is None else np.asarray(null)
        want_null = zeros if want_null is None else want_null
        bad = np.where(null | want_null, null != want_null, bad)
    if expected.unchanged is not None and unchanged is not None:
        # marked unchanged on both sides: nothing was sent, nothing to hold
        sent_unchanged = expected.unchanged if rows is None \
            else expected.unchanged[rows]
        bad &= ~(sent_unchanged & np.asarray(unchanged))
    return bad


def _rows_differ(table: dict, got_cols: list, expected: list, rows, n: int,
                 only=None) -> np.ndarray:
    """bool[n]: rows in which any column (of `only`, if given) differs."""
    bad = np.zeros(n, dtype=bool)
    for i, column in enumerate(table["columns"]):
        if only is None or i in only:
            bad |= _differs(column, got_cols[i], expected[i], rows, n)
    return bad


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------


def _key_numbers(table: dict, col_sets: list) -> list:
    """One int64 per row for each of `col_sets` (lists of key-column value
    arrays, all of the same table): equal exactly where the keys are equal.
    Integer-like key parts keep their values; text parts are numbered."""
    parts = []
    for j, i in enumerate(key_indices(table)):
        arrays = [np.asarray(cols[j]) for cols in col_sets]
        if table["columns"][i]["type"] in TEXT_TYPES \
                or arrays[0].dtype.kind not in "iub":
            flat = np.concatenate([a.astype("S") if a.dtype.kind != "S"
                                   else a for a in arrays] or [[]])
            _, inverse = np.unique(flat, return_inverse=True)
            cuts = np.cumsum([len(a) for a in arrays])[:-1]
            arrays = np.split(inverse.astype(np.int64), cuts)
        parts.append([a.astype(np.int64) for a in arrays])
    if len(parts) == 1:
        return parts[0]
    lo = [min((int(a.min()) for a in p if len(a)), default=0) for p in parts]
    span = [max((int(a.max()) for a in p if len(a)), default=0) - l + 1
            for p, l in zip(parts, lo)]
    if float(np.prod([float(s) for s in span])) >= 2.0**62:
        flat = np.concatenate([np.stack([p[k] for p in parts], axis=1)
                               for k in range(len(col_sets))])
        _, inverse = np.unique(flat, axis=0, return_inverse=True)
        cuts = np.cumsum([len(c[0]) for c in col_sets])[:-1]
        return np.split(inverse.astype(np.int64).ravel(), cuts)
    out = []
    for k in range(len(col_sets)):
        number = np.zeros(len(parts[0][k]), dtype=np.int64)
        for p, l, s in zip(parts, lo, span):
            number = number * s + (p[k] - l)
        out.append(number)
    return out


def _text_key(values) -> np.ndarray:
    """Key values a sink holds as text, as bytes the generator's compare to."""
    if isinstance(values, np.ndarray):
        return values
    as_list = values.to_pylist() if hasattr(values, "to_pylist") \
        else list(values)
    return np.array([(v or "").encode() for v in as_list], dtype="S") \
        if as_list else np.zeros(0, dtype="S1")


class SnapshotIndex:
    """Finds a table's snapshot row by primary key. Built once a run."""

    def __init__(self, table: dict, cols: list):
        self.table, self.cols = table, cols
        self.n = n_rows(cols)
        self.keys = key_indices(table)
        self.key_cols = [cols[i].values for i in self.keys]
        first = self.key_cols[0] if self.key_cols else None
        # the common case, found from the data and not from a name: one
        # integer key that counts up by one — a row's place is a subtraction
        self.dense = (len(self.keys) == 1 and isinstance(first, np.ndarray)
                      and first.dtype.kind in "iu" and self.n > 0
                      and bool((np.diff(first) == 1).all()))
        self.first = int(first[0]) if self.dense else 0

    def find(self, got_key_cols: list) -> np.ndarray:
        """int64[n]: the snapshot row each key belongs to, -1 for none."""
        if self.dense:
            idx = np.asarray(got_key_cols[0]).astype(np.int64) - self.first
            outside = (idx < 0) | (idx >= self.n)
            if outside.any():
                idx[outside] = -1
            return idx
        if not self.n:
            return np.full(len(got_key_cols[0]), -1, dtype=np.int64)
        got = [_text_key(v) if self.table["columns"][i]["type"] in TEXT_TYPES
               else np.asarray(v) for i, v in zip(self.keys, got_key_cols)]
        mine, theirs = _key_numbers(self.table, [self.key_cols, got])
        order = np.argsort(mine, kind="stable")
        at = np.clip(np.searchsorted(mine[order], theirs), 0, self.n - 1)
        return np.where(mine[order][at] == theirs, order[at], -1)


# ---------------------------------------------------------------------------
# the comparisons
# ---------------------------------------------------------------------------


def _key_cols(table: dict, cols: list) -> list:
    return [cols[i][0] for i in key_indices(table)]


def check_copy(index: SnapshotIndex, got: dict) -> dict:
    """Hold the rows one whole copy delivered to the table's snapshot.
    (The copy cell calls this between two copies, inside its window: a
    sound copy costs a subtraction, a compare a column and a bincount.)"""
    table, n = index.table, len(got["cols"][0][0]) if got["cols"] else 0
    idx = index.find(_key_cols(table, got["cols"]))
    known = idx >= 0
    picked, rows = got["cols"], idx
    if not known.all():
        rows = idx[known]
        picked = [tuple(None if a is None else _pick(a, known) for a in c)
                  for c in got["cols"]]
    wrong = _rows_differ(table, picked, index.cols, rows, len(rows))
    if "change" in got:
        wrong |= ~np.isin(np.asarray(got["change"])[known],
                          (GOT_INSERT, GOT_UPSERT))
    seen = np.bincount(rows, minlength=index.n)
    unseen = seen == 0
    mismatched = unseen
    if wrong.any():
        last_wrong = np.zeros(index.n, dtype=bool)
        last_wrong[rows] = wrong  # a repeated index keeps its last value
        mismatched = unseen | last_wrong
    unknown = n - len(rows)
    out = {"missing_rows": int(unseen.sum()),
           "wrong_rows": int(wrong.sum()),
           "unknown_rows": unknown, "misattributed_rows": 0,
           "state_mismatch_rows": int(mismatched.sum()) + unknown}
    return {"numbers": out,
            "info": {"rows_in_sink": int(n), "rows_required": index.n,
                     "duplicate_rows": int((seen > 1).sum())}}


def _pick(values, mask: np.ndarray):
    if isinstance(values, np.ndarray):
        return values[mask]
    if hasattr(values, "filter"):  # pyarrow
        import pyarrow as pa

        return values.filter(pa.array(mask))
    return [v for v, keep in zip(values, mask.tolist()) if keep]


def _take(values, rows: np.ndarray):
    if isinstance(values, np.ndarray):
        return values[rows]
    if hasattr(values, "take"):  # pyarrow
        import pyarrow as pa

        return values.take(pa.array(rows, type=pa.int64()))
    return [values[i] for i in rows.tolist()]


def check_cdc(tables: list, snapshot: dict, stream, kinds: np.ndarray,
              sent_events: int, need_events: int, got: dict) -> dict:
    """Hold what a sink received of the change stream to the log.

    `kinds` = wire.old_kinds(tables, stream) (what old image each event
    carried); the first `sent_events` events of the log were sent at all,
    the first `need_events` lie under the reported flush position and MUST
    be in the sink. `got[table id]` holds that table's delivered rows in
    delivery order: `cols` (values, null, unchanged per column), `change`,
    `commit_lsn`, `tx_ordinal`, and where the sink keeps them `old` (`rows`,
    `is_key`, `cols`: the old or key images of updates) and `delete_is_key`.
    """
    layout = stream.layout
    starts = layout.starts
    local = stream.local_index()
    seen = np.zeros(len(stream.op), dtype=bool)
    out = dict.fromkeys(LIMITS, 0)
    rows_in_sink = duplicates = 0
    for t, table in enumerate(tables):
        rows_got = got.get(int(table["id"]))
        ev = stream.events.get(t)
        mine = np.flatnonzero(stream.table == t) if local is not None \
            else None
        if rows_got is None or not len(rows_got["commit_lsn"]):
            continue
        n = len(rows_got["commit_lsn"])
        rows_in_sink += n
        commit = np.asarray(rows_got["commit_lsn"]).astype(np.int64)
        ordinal = np.asarray(rows_got["tx_ordinal"]).astype(np.int64)
        k = np.clip(np.searchsorted(layout.commit_lsn, commit), 0,
                    max(0, len(layout.rows) - 1))
        known = np.zeros(n, dtype=bool) if not len(layout.rows) else (
            (layout.commit_lsn[k] == commit) & (ordinal >= 0)
            & (ordinal < layout.rows[k]))
        e = np.where(known, starts[k] + ordinal, 0)
        known &= e < sent_events
        out["unknown_rows"] += int((~known).sum())
        same_table = known & (stream.table[e] == t)
        at = np.flatnonzero(same_table)      # delivered rows to compare
        e_at = e[at]
        ev_at = e_at if local is None else local[e_at]  # their table rows
        op = stream.op[e_at]
        m = len(at)
        out["misattributed_rows"] += int((known & ~same_table).sum())
        if not m:
            continue
        picked = [tuple(None if a is None else _take(a, at) for a in c)
                  for c in rows_got["cols"]]
        keys = set(key_indices(table))
        change = np.asarray(rows_got["change"])[at]
        # a sink that keeps no old images may hold an update that moved a
        # row as two rows at the update's coordinates: a delete of the key
        # it left, then the new row
        left = np.zeros(m, dtype=bool)
        if rows_got.get("old") is None and ev.old is not None:
            left = (op == UPDATE) & (change == GOT_DELETE) \
                & (kinds[e_at] != 0) & _moved(table, ev, ev_at)
        # the image a delivered row carries: the new row, or for a delete
        # the old one (its key alone under replica identity default)
        is_delete = (op == DELETE) | left
        want = ev.new if ev.old is None else [
            _merge(n_col, o_col, ev_at, is_delete)
            for n_col, o_col in zip(ev.new, ev.old)]
        rows = None if ev.old is not None else ev_at
        wrong_key = _rows_differ(table, picked, want, rows, m, keys)
        key_only = is_delete & (kinds[e_at] == ord("K"))
        rest = [i for i in range(len(table["columns"])) if i not in keys]
        wrong = np.zeros(m, dtype=bool)
        for i in rest:
            bad = _differs(table["columns"][i], picked[i], want[i], rows, m)
            null = picked[i][1]
            # a key image carries nothing but the key: the rest is NULL
            bad = np.where(key_only, False if null is None
                           else ~np.asarray(null), bad)
            wrong |= bad
        wrong |= ~np.where(
            change == GOT_UPSERT, ~is_delete,
            change == np.select([is_delete, op == INSERT],
                                [GOT_DELETE, GOT_INSERT], GOT_UPDATE))
        wrong |= _old_images_differ(table, rows_got, at, ev, ev_at, op,
                                    kinds[e_at])
        wrong &= ~wrong_key
        out["misattributed_rows"] += int(wrong_key.sum())
        out["wrong_rows"] += int(wrong.sum())
        duplicates += int((~left).sum()) - len(np.unique(e_at[~left]))
        seen[e_at[~left]] = True
        out["state_mismatch_rows"] += _state_mismatches(
            table, snapshot.get(int(table["id"])), ev, mine, stream.op,
            rows_got, at, e_at)
    out["missing_rows"] = int((~seen[:need_events]).sum())
    return {"numbers": out,
            "info": {"rows_in_sink": int(rows_in_sink),
                     "rows_required": int(need_events),
                     "duplicate_rows": int(duplicates)}}


def _moved(table: dict, ev, rows: np.ndarray) -> np.ndarray:
    """bool[len(rows)]: events of one table whose old and new keys differ."""
    out = np.zeros(len(rows), dtype=bool)
    for i in key_indices(table):
        a, b = ev.new[i].values, ev.old[i].values
        if not isinstance(a, bytes):
            out |= a[rows] != b[rows]
    return out


def _merge(new, old, rows: np.ndarray, use_old: np.ndarray):
    """The Col of `rows` that takes `old` where `use_old` and `new`
    elsewhere (rows already picked: compare it with rows=None)."""
    from oplog import Col

    a, b = new.pick(rows), old.pick(rows)
    if isinstance(a.values, bytes) or isinstance(b.values, bytes):
        values = a.values  # one value shared by every row, old or new
    else:
        values = np.where(use_old, b.values, a.values)

    def mask(x, y):
        if x is None and y is None:
            return None
        zeros = np.zeros(len(rows), dtype=bool)
        return np.where(use_old, zeros if y is None else y,
                        zeros if x is None else x)

    return Col(values, mask(a.null, b.null), mask(a.unchanged, None))


def _old_images_differ(table, rows_got, at, ev, ev_at, op, kinds_at):
    """bool[m]: updates whose old or key image is not the one the source
    sent — there where none was sent, absent where one was, a key image
    for a whole row, or other values."""
    m = len(at)
    old = rows_got.get("old")
    if old is None:  # this sink keeps no old images (it was sent none)
        return np.zeros(m, dtype=bool)
    place = np.full(len(rows_got["commit_lsn"]), -1, dtype=np.int64)
    place[np.asarray(old["rows"], dtype=np.int64)] = np.arange(
        len(old["rows"]))
    j = place[at]
    is_update = op == UPDATE
    expected = is_update & (kinds_at != 0)
    bad = is_update & ((j >= 0) != expected)
    both = np.flatnonzero(expected & (j >= 0))
    if len(both):
        jj = j[both]
        as_key = np.asarray(old["is_key"])[jj]
        bad[both] |= as_key != (kinds_at[both] == ord("K"))
        picked = [tuple(None if a is None else _take(a, jj) for a in c)
                  for c in old["cols"]]
        keys = set(key_indices(table))
        rows = ev_at[both]
        bad[both] |= _rows_differ(table, picked, ev.old, rows, len(both),
                                  keys)
        full = ~as_key
        for i in range(len(table["columns"])):
            if i not in keys:
                bad[both] |= full & _differs(table["columns"][i], picked[i],
                                             ev.old[i], rows, len(both))
    if rows_got.get("delete_is_key") is not None:
        as_key = np.asarray(rows_got["delete_is_key"])[at]
        bad |= (op == DELETE) & (as_key != (kinds_at == ord("K")))
    return bad


def _state_mismatches(table: dict, snap_cols, ev, mine, op_all, rows_got,
                      at: np.ndarray, e_at: np.ndarray) -> int:
    """Keys of one table that the sink's rows `at` (matched to the log's
    events `e_at`), replayed in delivery order over the snapshot, leave
    differently than the log does up to the last of them. `mine`: the
    table's events in the log (None: it has them all)."""
    keys = key_indices(table)
    last = int(e_at.max())
    upto = last + 1 if mine is None \
        else int(np.searchsorted(mine, last, side="right"))
    events = np.arange(upto) if mine is None else mine[:upto]
    op = op_all[events] if mine is not None else op_all[:upto]

    def plain(cols, rows):
        return [_text_key(v) if table["columns"][i]["type"] in TEXT_TYPES
                else np.asarray(v)
                for i, v in ((i, _take(cols[i][0], rows)) for i in keys)]

    log_new = [ev.new[i].values[:upto] for i in keys]
    log_old = log_new if ev.old is None \
        else [ev.old[i].values[:upto] for i in keys]
    # the sink's old images: an update that moved a row names the key it left
    old = rows_got.get("old")
    moved_at = np.zeros(0, dtype=np.int64)
    moved_keys = [a[:0] for a in log_new]
    if old is not None and len(old["rows"]):
        place = np.full(len(rows_got["commit_lsn"]), -1, dtype=np.int64)
        place[at] = np.arange(len(at))
        moved_at = place[np.asarray(old["rows"], dtype=np.int64)]
        moved_keys = plain(old["cols"], np.flatnonzero(moved_at >= 0))
        moved_at = moved_at[moved_at >= 0]
    snap_keys = [snap_cols[i].values for i in keys] \
        if snap_cols and n_rows(snap_cols) else [a[:0] for a in log_new]
    (i_new, i_old, i_got, i_snap, i_moved), u = _key_ids(table, [
        log_new, log_old, plain(rows_got["cols"], at), snap_keys, moved_keys])
    in_snapshot = np.zeros(u, dtype=bool)
    in_snapshot[i_snap] = True
    # the log: an update that changes the key deletes the old one first
    want = _replay(
        u, in_snapshot,
        np.stack([np.where((op == UPDATE) & (i_new != i_old), i_old, -1),
                  np.where(op == DELETE, i_old, i_new)], axis=1).ravel(),
        np.stack([np.ones(upto, dtype=bool), op == DELETE], axis=1).ravel(),
        np.repeat(events, 2))
    # the sink, in delivery order
    first = np.full(len(at), -1, dtype=np.int64)
    left = i_moved != i_got[moved_at]
    first[moved_at[left]] = i_moved[left]
    got_delete = np.asarray(rows_got["change"])[at] == GOT_DELETE
    have = _replay(
        u, in_snapshot, np.stack([first, i_got], axis=1).ravel(),
        np.stack([np.ones(len(at), dtype=bool), got_delete], axis=1).ravel(),
        np.repeat(e_at, 2))
    return int(((want[0] != have[0])
                | (want[0] & (want[1] != have[1]))).sum())


def _key_ids(table: dict, col_sets: list) -> tuple:
    """The keys of `col_sets` numbered 0..u-1, equal keys alike: (one id
    array per set, u)."""
    numbers = _key_numbers(table, col_sets)
    flat = np.concatenate(numbers)
    if not len(flat):
        return numbers, 0
    lo, hi = int(flat.min()), int(flat.max())
    if hi - lo < 4 * len(flat) + 1024:  # near dense: no sort needed
        return [a - lo for a in numbers], hi - lo + 1
    uniq, inverse = np.unique(flat, return_inverse=True)
    cuts = np.cumsum([len(a) for a in numbers])[:-1]
    return np.split(inverse, cuts), len(uniq)


def _replay(u: int, in_snapshot: np.ndarray, key_ids: np.ndarray,
            deletes: np.ndarray, writers: np.ndarray):
    """(present[u], writer[u]) after the changes `key_ids` (-1: none) in
    order: a delete leaves the key absent, anything else leaves it present
    and written by `writers`; an untouched key is as the snapshot has it."""
    live = key_ids >= 0
    key_ids, deletes, writers = key_ids[live], deletes[live], writers[live]
    last = np.full(u, -1, dtype=np.int64)
    last[key_ids] = np.arange(len(key_ids))  # a repeated key keeps its last
    touched = last >= 0
    present = in_snapshot.copy()
    present[touched] = ~deletes[last[touched]]
    writer = np.full(u, -1, dtype=np.int64)
    writer[touched] = writers[last[touched]]
    return present, writer


def verify_received(tables: list, snapshot: dict, stream, sent: int,
                    need: int, received: dict) -> dict:
    """Hold what a sink received to the reference: each table's copied
    rows to its snapshot, its delivered changes to the log. `received` is
    {table id: {"copy": rows or None, "cdc": rows or None}}."""
    import wire

    verdict = check_cdc(
        tables, snapshot, stream, wire.old_kinds(tables, stream), sent, need,
        {tid: rows["cdc"] for tid, rows in received.items()
         if rows["cdc"] is not None})
    check_copies({int(t["id"]): SnapshotIndex(
        t, snapshot.get(int(t["id"])) or []) for t in tables},
        received, verdict["numbers"])
    return verdict


def check_copies(truth: dict, received: dict, numbers: dict) -> None:
    """Add to `numbers` what each table's copied rows read against its
    snapshot (`truth`: {table id: SnapshotIndex})."""
    for tid, index in truth.items():
        copied = (received.get(tid) or {}).get("copy")
        if copied is None:
            numbers["missing_rows"] += index.n
            numbers["state_mismatch_rows"] += index.n
            continue
        for k, v in check_copy(index, copied)["numbers"].items():
            numbers[k] += v
