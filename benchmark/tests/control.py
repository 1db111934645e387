#!/usr/bin/env python3
"""The control and the planted faults: the rest of a run, with the timed
path broken underneath, has to come out `correct: false`.

    python3 benchmark/tests/control.py --fault ack_and_drop --workload <cell> \\
        --seed <n> --seconds <s> [--rehearse]

The system runs no model and states no precision, so the control breaks the
one guarantee the configurations state, at-least-once delivery:

  ack_and_drop   the destination acknowledges every write and drops each
                 fifth one (the control)
  half_batch     the sink keeps the first half of every batch and leaves the
                 rest out (null-sink cells)
  altered_value  one decoded value per batch is altered where it is
                 produced (`DeviceDecoder._complete`)

and, for a deployment with updates and deletes (the fixture of
`tests/data/`, run with --config-file / --traffic-file), one delivered
answer altered in what the null sink holds, each caught by the number named:

  update_dropped    the last update of a table is left out
                    (state_mismatch_rows)
  delete_as_upsert  a delete arrives labelled as an update (wrong_rows)
  updates_swapped   the two updates of one row change places in delivery
                    order (state_mismatch_rows)
  numeric_digit     one digit of one NUMERIC value (wrong_rows)
  null_to_empty     a NULL text arrives as an empty string (wrong_rows)
  other_table       a row claims the coordinates of the other table's
                    event (misattributed_rows)

and for the wide fixture (`docs-*.json` of `tests/data/`, JSON columns and
an out-of-line TOAST body):

  json_value_changed  one delivered JSON value is another valid one
                      (wrong_rows)
  toast_as_null       an unchanged-TOAST cell arrives as NULL (wrong_rows)
  json_reserialised   every JSON value the sink keeps is spelled again
                      with other spacing and key order: the same values,
                      so the run stays `correct: true` — the comparison
                      is by value

On the chip it is run at the cell's own size on three seeds or more
(PERF.md); `test_control.py` keeps it at rehearsal size.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as harness  # noqa: E402

FAULTS = ("ack_and_drop", "half_batch", "altered_value", "update_dropped",
          "delete_as_upsert", "updates_swapped", "numeric_digit",
          "null_to_empty", "other_table", "json_value_changed",
          "toast_as_null", "json_reserialised")
JSON_OIDS = (114, 3802)


def plant(fault: str) -> None:
    if fault == "ack_and_drop":
        from etl_tpu.destinations.base import WriteAck

        inner_make = harness.Run.make_pipeline

        def make_pipeline(self, port, destination, pipeline_id=1):
            calls = {"n": 0}
            for attr in ("write_event_batches", "write_table_batch"):
                real = getattr(destination, attr)

                async def lossy(*args, _real=real, **kw):
                    calls["n"] += 1
                    if calls["n"] % 5 == 0:
                        return WriteAck.durable()
                    return await _real(*args, **kw)

                setattr(destination, attr, lossy)
            return inner_make(self, port, destination, pipeline_id)

        harness.Run.make_pipeline = make_pipeline
    elif fault == "half_batch":
        cls = type(harness.make_null_destination())
        keep = cls._keep

        def half(self, e):
            keep(self, e)
            tid, columns, *rest = self.parts[-1]
            n = len(rest[0]) // 2
            self.parts[-1] = (
                tid, [(data[:n], valid[:n], toast, lazy)
                      for data, valid, toast, lazy in columns],
                *(p[:n] if p is not None and not isinstance(p, tuple) else p
                  for p in rest))

        cls._keep = half
    elif fault == "altered_value":
        import numpy as np

        from etl_tpu.ops.engine import DeviceDecoder

        complete = DeviceDecoder._complete

        def altered(self, *args, **kw):
            batch = complete(self, *args, **kw)
            col = batch.columns[2]
            data = np.array(col.data)
            data[0] += 1
            col.data = data
            return batch

        DeviceDecoder._complete = altered
    elif fault == "json_reserialised":
        cls = type(harness.make_null_destination())
        columns = cls._columns

        def respelt(batch):
            out = columns(batch)
            for i, c in enumerate(batch.columns):
                if c.schema.type_oid in JSON_OIDS:
                    out[i] = _respelt(*out[i])
            return out

        cls._columns = staticmethod(respelt)
    elif fault in ALTER:
        cls = type(harness.make_null_destination())
        received = cls.received

        def altered_received(self, tables):
            out = received(self, tables)
            ALTER[fault](tables, out)
            return out

        cls.received = altered_received
    else:
        raise SystemExit(f"unknown fault {fault!r}; one of {FAULTS}")


def _listed(values) -> list:
    return values.to_pylist() if hasattr(values, "to_pylist") \
        else list(values)


def _reorder(rows: dict, order) -> None:
    """Deliver one table's rows in another order (or only some of them)."""
    import numpy as np

    def taken(a, rows):
        if isinstance(a, np.ndarray):
            return a[rows]
        listed = _listed(a)
        return [listed[i] for i in rows]

    order = np.asarray(order)
    for k in ("change", "commit_lsn", "tx_ordinal", "delete_is_key"):
        if rows.get(k) is not None:
            rows[k] = rows[k][order]
    rows["cols"] = [tuple(None if a is None else taken(a, order.tolist())
                          for a in c) for c in rows["cols"]]
    if rows.get("old") is not None:
        place = {int(r): i for i, r in enumerate(order.tolist())}
        held = [j for j, r in enumerate(rows["old"]["rows"].tolist())
                if int(r) in place]
        rows["old"]["rows"] = np.array(
            [place[int(rows["old"]["rows"][j])] for j in held],
            dtype=np.int64)
        rows["old"]["is_key"] = rows["old"]["is_key"][held]
        rows["old"]["cols"] = [tuple(None if a is None else taken(a, held)
                                     for a in c)
                               for c in rows["old"]["cols"]]


def _update_dropped(tables, got) -> None:
    import numpy as np

    rows = got[int(tables[1]["id"])]["cdc"]
    # not the last one: what follows it shows that it is missing
    last = int(np.flatnonzero(rows["change"] == 1)[-2])
    _reorder(rows, np.delete(np.arange(len(rows["change"])), last))


def _delete_as_upsert(tables, got) -> None:
    import numpy as np

    rows = got[int(tables[1]["id"])]["cdc"]
    rows["change"][int(np.flatnonzero(rows["change"] == 2)[0])] = 1


def _updates_swapped(tables, got) -> None:
    import numpy as np

    rows = got[int(tables[1]["id"])]["cdc"]
    key = np.asarray(rows["cols"][0][0])
    old_key = np.asarray(rows["old"]["cols"][0][0])
    # the first update that moved a row, and the earlier one of the same row
    for j, second in enumerate(rows["old"]["rows"].tolist()):
        earlier = np.flatnonzero((key[:second] == old_key[j])
                                 & (rows["change"][:second] == 1))
        if len(earlier):
            order = np.arange(len(key))
            order[[int(earlier[-1]), second]] = second, int(earlier[-1])
            _reorder(rows, order)
            return
    raise SystemExit("no row was updated twice in what the sink holds")


def _numeric_digit(tables, got) -> None:
    rows = got[int(tables[0]["id"])]["cdc"]
    i = next(i for i, c in enumerate(tables[0]["columns"])
             if c["type"] == "numeric")
    values, null, toast = rows["cols"][i]
    values = _listed(values)
    at = next(k for k, v in enumerate(values) if v is not None)
    last = values[at][-1]
    values[at] = values[at][:-1] + ("1" if last != "1" else "2")
    rows["cols"][i] = (values, null, toast)


def _null_to_empty(tables, got) -> None:
    import numpy as np
    import pyarrow as pa

    rows = got[int(tables[0]["id"])]["cdc"]
    i = next(i for i, c in enumerate(tables[0]["columns"])
             if c["type"] == "varchar" and c.get("nullable"))
    values, null, toast = rows["cols"][i]
    at = int(np.flatnonzero(null)[0])
    values = _listed(values)
    values[at] = ""
    null = null.copy()
    null[at] = False
    rows["cols"][i] = (pa.array(values, type=pa.string()), null, toast)


def _other_table(tables, got) -> None:
    mine = got[int(tables[0]["id"])]["cdc"]
    theirs = got[int(tables[1]["id"])]["cdc"]
    mine["commit_lsn"][0] = theirs["commit_lsn"][0]
    mine["tx_ordinal"][0] = theirs["tx_ordinal"][0]


def _respelt(data, valid, toast, lazy) -> tuple:
    """A JSON column's kept cells as JSON text spelled another way: keys in
    reverse order, other spacing, non-ASCII unescaped."""
    import json

    import pyarrow as pa

    from etl_tpu.models import JSON_NULL

    def turned(v):
        if isinstance(v, dict):
            return {k: turned(v[k]) for k in sorted(v, reverse=True)}
        if isinstance(v, list):
            return [turned(x) for x in v]
        return None if v is JSON_NULL else v

    text = isinstance(data, (pa.Array, pa.ChunkedArray))
    texts = [None if v is None or not ok else json.dumps(
        turned(json.loads(v) if text else v), indent=1,
        separators=(" ,", " :  "), ensure_ascii=False)
        for v, ok in zip(_listed(data), valid.tolist())]
    return pa.array(texts, type=pa.string()), valid, toast, lazy


def _json_cells(tables, got):
    """The cdc rows and the index of the first JSON column of the first
    table that has one."""
    import oplog

    for table in tables:
        for i, c in enumerate(table["columns"]):
            if oplog.comparison(c) == "json":
                return got[int(table["id"])]["cdc"], i
    raise SystemExit("no JSON column in the configuration")


def _json_value_changed(tables, got) -> None:
    rows, i = _json_cells(tables, got)
    values, null, toast = rows["cols"][i]
    values = _listed(values)
    at = next(k for k, v in enumerate(values) if v is not None)
    values[at] = "[" + values[at] + "]"  # still JSON, another value
    rows["cols"][i] = (values, null, toast)


def _toast_as_null(tables, got) -> None:
    import numpy as np

    for table in tables:
        rows = got[int(table["id"])]["cdc"]
        for i, (values, null, toast) in enumerate(rows["cols"]):
            if toast is not None and toast.any():
                at = int(np.flatnonzero(toast)[0])
                toast, null = toast.copy(), (
                    np.zeros(len(toast), dtype=bool) if null is None
                    else null.copy())
                toast[at], null[at] = False, True
                rows["cols"][i] = (values, null, toast)
                return
    raise SystemExit("no unchanged-TOAST cell in what the sink holds")


ALTER = {"update_dropped": _update_dropped,
         "delete_as_upsert": _delete_as_upsert,
         "updates_swapped": _updates_swapped,
         "numeric_digit": _numeric_digit, "null_to_empty": _null_to_empty,
         "other_table": _other_table,
         "json_value_changed": _json_value_changed,
         "toast_as_null": _toast_as_null}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    fault = argv[argv.index("--fault") + 1]
    del argv[argv.index("--fault"):argv.index("--fault") + 2]
    plant(fault)
    return harness.main(argv)


if __name__ == "__main__":
    sys.exit(main())
