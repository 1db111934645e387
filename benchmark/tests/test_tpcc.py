"""The TPC-C cell (`tpcc-w4-null.standard-mix-drain`): its files load by
name, the sound program comes out `correct: true` at rehearsal size on the
CPU, and one delivered answer altered in what the null sink holds comes out
`correct: false` by the number that should catch it.

`control.py`'s own faults address `tables[0]` and `tables[1]`; of the
configuration's tables those are `warehouse` and `district`, so
`numeric_digit` (a digit of `w_tax`) fits as it is, and the rest are
planted from here, on the tables that have the deletes, the NULLs and the
repeated updates:

  tpcc_update_dropped    the last update of one district row is left out
                         (state_mismatch_rows)
  tpcc_delete_as_upsert  a new_order delete arrives labelled as an update
                         (wrong_rows)
  tpcc_updates_swapped   two updates of one district row change places in
                         delivery order (state_mismatch_rows, alone)
  tpcc_null_carrier      a NULL o_carrier_id arrives as a value — the zero
                         an empty string parses to (wrong_rows)
  tpcc_other_table       an orders row claims the coordinates of a
                         new_order event (misattributed_rows)

Run as a script it is `control.py` with these faults added:

    python3 benchmark/tests/test_tpcc.py --fault tpcc_null_carrier \\
        --workload tpcc-w4-null.standard-mix-drain --seed <n> --seconds <s>
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import control  # noqa: E402

CELL = "tpcc-w4-null.standard-mix-drain"
DISTRICT, NEW_ORDER, ORDERS = 1, 3, 4  # places in the configuration's tables
GOT_UPDATE, GOT_DELETE = 1, 2


def _cdc(tables, got, t: int) -> dict:
    return got[int(tables[t]["id"])]["cdc"]


def _district_keys(rows: dict):
    import numpy as np

    return np.asarray(rows["cols"][1][0]) * 100 + np.asarray(rows["cols"][0][0])


def _update_dropped(tables, got) -> None:
    import numpy as np

    rows = _cdc(tables, got, DISTRICT)
    key = _district_keys(rows)
    # the last update of a row that is not the table's last event: what
    # follows it shows that it is missing
    at = next(j for j in range(len(key) - 2, -1, -1)
              if key[j] not in key[j + 1:])
    control._reorder(rows, np.delete(np.arange(len(key)), at))


def _delete_as_upsert(tables, got) -> None:
    import numpy as np

    rows = _cdc(tables, got, NEW_ORDER)
    rows["change"][int(np.flatnonzero(rows["change"] == GOT_DELETE)[0])] = \
        GOT_UPDATE


def _updates_swapped(tables, got) -> None:
    import numpy as np

    rows = _cdc(tables, got, DISTRICT)
    key = _district_keys(rows)
    last = len(key) - 1
    earlier = np.flatnonzero(key[:last] == key[last])
    if not len(earlier):
        raise SystemExit("no district row was updated twice")
    order = np.arange(len(key))
    order[[int(earlier[-1]), last]] = last, int(earlier[-1])
    control._reorder(rows, order)


def _null_carrier(tables, got) -> None:
    import numpy as np

    rows = _cdc(tables, got, ORDERS)
    i = next(i for i, c in enumerate(tables[ORDERS]["columns"])
             if c["name"] == "o_carrier_id")
    values, null, toast = rows["cols"][i]
    null = np.array(null)
    null[int(np.flatnonzero(null)[0])] = False
    rows["cols"][i] = (values, null, toast)


def _other_table(tables, got) -> None:
    mine, theirs = _cdc(tables, got, ORDERS), _cdc(tables, got, NEW_ORDER)
    mine["commit_lsn"][0] = theirs["commit_lsn"][0]
    mine["tx_ordinal"][0] = theirs["tx_ordinal"][0]


FAULTS = {"tpcc_update_dropped": _update_dropped,
          "tpcc_delete_as_upsert": _delete_as_upsert,
          "tpcc_updates_swapped": _updates_swapped,
          "tpcc_null_carrier": _null_carrier,
          "tpcc_other_table": _other_table}

CASES = [  # (fault, correct, the number that catches it, numbers that stay 0)
    (None, True, None, ()),
    ("tpcc_update_dropped", False, "state_mismatch_rows", ("wrong_rows",)),
    ("tpcc_delete_as_upsert", False, "wrong_rows", ("missing_rows",)),
    ("tpcc_updates_swapped", False, "state_mismatch_rows",
     ("missing_rows", "wrong_rows", "unknown_rows", "misattributed_rows")),
    ("numeric_digit", False, "wrong_rows", ("missing_rows",)),
    ("tpcc_null_carrier", False, "wrong_rows", ("missing_rows",)),
    ("tpcc_other_table", False, "misattributed_rows", ("wrong_rows",)),
    ("ack_and_drop", False, "missing_rows", ("wrong_rows",)),
]


def test_the_cell_s_files_load_by_name():
    import run as harness

    cell = harness.Cell(CELL, rehearse=False)
    assert cell.chips == 1 and cell.traffic["kind"] == "backlog"
    assert [t["name"].split(".")[1] for t in cell.tables] == [
        "warehouse", "district", "customer", "new_order", "orders",
        "order_line", "stock", "item"]
    assert cell.generator.__name__ == "deployment_tpcc"
    assert [m["name"] for m in cell.end_to_end] == ["cdc_events_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    # at least PR 33's seven and the drain's 21: later PRs add metrics
    assert sum(n.startswith("tpcc_") for n in names) >= 7
    assert sum(n.startswith("drain_") for n in names) >= 21
    assert all(cell.readers[n]["reader"] for n in names)
    assert cell.config["destination"] == {"type": "null"}
    assert cell.config["guarantee"] == "at-least-once"
    rehearsed = harness.Cell(CELL, rehearse=True)
    assert rehearsed.config["warehouses"] == 1
    assert rehearsed.traffic["generator"]["bulk_rows"] < 1024


# sha256 over what the source of commit d2b55c6 prebuilds for the cell's
# configuration at --seed 2147483659 --seconds 2 and the rehearsal sizes:
# every table's COPY stream and its row offsets, every transaction's send
# buffer, the summed payload bytes and the layout's four arrays. The
# ClickHouse cell's source is the same bytes.
GOLDEN = "0cadb6dd3daf12b66c7bcba73016c88a54af3d8dd1b21c9afbd5fbfbc89f6565"


@pytest.mark.parametrize("config", ["tpcc-w4-null", "tpcc-w4-clickhouse"])
def test_the_source_s_bytes_are_the_parent_s(config):
    import hashlib

    import numpy as np

    import oplog
    import source

    bench = os.path.dirname(HERE)
    path = os.path.join(bench, "configs", config + ".json")
    with open(path) as f:
        doc = json.load(f)
    with open(os.path.join(bench, "traffic", "standard-mix-drain.json")) as f:
        traffic = json.load(f)
    doc.update(doc["rehearsal"])
    traffic.update(traffic["rehearsal"])
    plan = source.Plan(doc, traffic, 2147483659, 2.0,
                       oplog.load_generator(doc, path))
    plan.render()
    h = hashlib.sha256()
    for table in plan.tables:
        blob, offsets = plan.copies[table["name"]]
        h.update(bytes(blob))
        h.update(np.asarray(offsets, dtype=np.int64).tobytes())
    for buf in plan.bufs:
        h.update(buf)
    h.update(str(plan.payload_bytes).encode())
    lay = plan.layout
    for a in (lay.rows, lay.begin_lsn, lay.commit_lsn, lay.end_lsn):
        h.update(np.asarray(a, dtype=np.int64).tobytes())
    assert h.hexdigest() == GOLDEN


@pytest.mark.parametrize("fault,correct,number,zeros", CASES,
                         ids=[c[0] or "sound" for c in CASES])
def test_fault_decides_correct(fault, correct, number, zeros):
    script = __file__ if fault else os.path.join(HERE, "..", "run.py")
    cmd = [sys.executable, script, "--workload", CELL, "--seed", "2147483659",
           "--seconds", "2", "--trace", "0", "--rehearse",
           *(["--fault", fault] if fault else [])]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct, line["checks"]
    checks = line["checks"]
    if number:
        assert checks[number]["value"] > checks[number]["limit"]
    else:
        assert all(c["value"] == 0 for c in checks.values())
    for name in zeros:
        assert checks[name]["value"] == 0, checks
    assert "check missing_rows:" in out.stderr
    assert list(line)[-1] == "checks"


def main(argv=None) -> int:
    control.ALTER.update(FAULTS)
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
