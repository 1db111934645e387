"""The TPC-C stream into ClickHouse (`tpcc-w4-clickhouse.standard-mix-drain`):
its files load by name, the sound program comes out `correct: true` at
rehearsal size on the CPU, and the control and three planted faults come
out `correct: false` by the number that should catch each.

`control.py` plants a delivered answer in what the *null sink* holds
(`control.ALTER` rewrites `NullDestination.received`, in the pipeline's
process). This cell's sink is `benchmark/sink.py`, a process of its own
that parses the bytes it was sent, so the same three answers are altered
one step earlier, in the one place the program and that sink share: the
TSV body of an INSERT on its way out (`ClickHouseDestination._insert_tsv`).
Each alters one field of one row of a CDC write, once a run:

  ch_numeric_digit     one digit of one `ol_amount` (wrong_rows)
  ch_delete_as_upsert  a `new_order` delete arrives labelled UPSERT
                       (wrong_rows, and the row is still there:
                       state_mismatch_rows)
  ch_null_as_empty     a NULL `o_carrier_id` arrives as the `0` an empty
                       field parses to (wrong_rows) — the stream has no
                       NULL in a text column, and an empty field of a
                       Nullable(Int32) column is what ClickHouse would
                       make a 0 of (`input_format_tsv_empty_as_default`)

and the control is `control.py`'s own: `ack_and_drop`, every fifth write
acknowledged and dropped (missing_rows).

Run as a script it is `control.py` with these faults added:

    python3 benchmark/tests/test_tpcc_clickhouse.py --fault ch_numeric_digit \\
        --workload tpcc-w4-clickhouse.standard-mix-drain --seed <n> --seconds <s>
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, BENCH)

import control  # noqa: E402

CELL = "tpcc-w4-clickhouse.standard-mix-drain"
SEED = "2147483659"
COPIED = b"0" * 16 + b"/"  # a copied row's sequence key has no commit


def _alter_field(body: bytes, column: int, change) -> "bytes | None":
    """`body` with `change(field)` in place of one field of its first CDC
    row for which `change` gives other bytes; None where no row has one."""
    lines = body.split(b"\n")
    for i, line in enumerate(lines[:-1]):
        fields = line.split(b"\t")
        if fields[-1].startswith(COPIED):
            continue
        new = change(fields[column])
        if new is not None and new != fields[column]:
            fields[column] = new
            lines[i] = b"\t".join(fields)
            return b"\n".join(lines)
    return None


def _digit(field: bytes) -> "bytes | None":
    if field == b"\\N":
        return None
    return field[:-1] + (b"1" if field[-1:] != b"1" else b"2")


# fault -> (the destination's table name, the field's place, the change)
BODY_FAULTS = {
    # ol_amount is the ninth of order_line's ten columns
    "ch_numeric_digit": ("public_order__line", 8, _digit),
    # the change label is the field before the sequence key
    "ch_delete_as_upsert": ("public_new__order", -2, lambda f:
                            b"UPSERT" if f == b"DELETE" else None),
    # o_carrier_id is the sixth of orders' eight columns
    "ch_null_as_empty": ("public_orders", 5, lambda f:
                         b"0" if f == b"\\N" else None),
}


def plant(fault: str) -> None:
    from etl_tpu.destinations.clickhouse import ClickHouseDestination

    table, column, change = BODY_FAULTS[fault]
    insert_tsv = ClickHouseDestination._insert_tsv
    done = {"n": 0}

    async def altered(self, name, schema, body):
        if not done["n"] and name == table:
            new = _alter_field(body, column, change)
            if new is not None:
                done["n"] += 1
                body = new
        return await insert_tsv(self, name, schema, body)

    ClickHouseDestination._insert_tsv = altered


CASES = [  # (fault, correct, the number that catches it, numbers that stay 0)
    (None, True, None, ()),
    ("ack_and_drop", False, "missing_rows", ("wrong_rows", "unknown_rows")),
    ("ch_numeric_digit", False, "wrong_rows", ("missing_rows",)),
    ("ch_delete_as_upsert", False, "wrong_rows", ("missing_rows",)),
    ("ch_null_as_empty", False, "wrong_rows", ("missing_rows",)),
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
    assert sum(n.startswith("drain_") for n in names) == 21
    assert sum(n.startswith("tpcc_") for n in names) == 7  # 6 + the render
    assert sum(n.startswith("ch_") for n in names) == 7  # 4 + three new
    assert all(cell.readers[n]["reader"] for n in names)
    assert cell.config["destination"] == {"type": "clickhouse",
                                          "database": "default"}
    assert cell.config["guarantee"] == "at-least-once"
    null = harness.Cell("tpcc-w4-null.standard-mix-drain", rehearse=False)
    assert cell.tables == null.tables and cell.traffic == null.traffic
    assert cell.traffic_path == null.traffic_path
    rehearsed = harness.Cell(CELL, rehearse=True)
    assert rehearsed.config["warehouses"] == 1


@pytest.mark.parametrize("body,column,change,want", [
    (b"1\t2.50\tUPSERT\t" + COPIED + b"x\n1\t2.50\tUPSERT\t0a/x\n", 1,
     _digit, b"1\t2.50\tUPSERT\t" + COPIED + b"x\n1\t2.51\tUPSERT\t0a/x\n"),
    (b"1\t\\N\tUPSERT\t0a/x\n1\t2.51\tUPSERT\t0b/x\n", 1, _digit,
     b"1\t\\N\tUPSERT\t0a/x\n1\t2.52\tUPSERT\t0b/x\n"),
    (b"1\tUPSERT\t0a/x\n2\tDELETE\t0b/x\n3\tDELETE\t0c/x\n", -2,
     BODY_FAULTS["ch_delete_as_upsert"][2],
     b"1\tUPSERT\t0a/x\n2\tUPSERT\t0b/x\n3\tDELETE\t0c/x\n"),
    (b"1\t5\tUPSERT\t0a/x\n", 1, BODY_FAULTS["ch_null_as_empty"][2], None),
], ids=["skips-copied-rows", "skips-nulls", "first-delete", "nothing-to-do"])
def test_a_fault_alters_one_field_of_one_cdc_row(body, column, change, want):
    assert _alter_field(body, column, change) == want


@pytest.mark.parametrize("fault,correct,number,zeros", CASES,
                         ids=[c[0] or "sound" for c in CASES])
def test_fault_decides_correct(fault, correct, number, zeros, tmp_path):
    # five times the file's rehearsal backlog, as tests/test_tpcc_deployment
    # rehearses the null cell (an idle CPU drains the file's own: C14)
    with open(os.path.join(BENCH, "traffic", "standard-mix-drain.json")) as f:
        traffic = json.load(f)
    traffic["rehearsal"]["backlog_events_per_second"] = 100_000
    mix = tmp_path / "standard-mix-drain.json"
    mix.write_text(json.dumps(traffic))
    script = __file__ if fault else os.path.join(BENCH, "run.py")
    cmd = [sys.executable, script, "--workload", CELL, "--seed", SEED,
           "--seconds", "2", "--trace", "0", "--rehearse",
           "--traffic-file", str(mix),
           *(["--fault", fault] if fault else [])]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct, line["checks"]
    checks = line["checks"]
    if number:
        assert checks[number]["value"] >= 1 > checks[number]["limit"]
    else:
        assert all(c["value"] == 0 for c in checks.values())
    for name in zeros:
        assert checks[name]["value"] == 0, checks
    assert "check missing_rows:" in out.stderr
    assert list(line)[-1] == "checks"


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    fault = argv[argv.index("--fault") + 1]
    if fault not in BODY_FAULTS:
        return control.main(argv)
    del argv[argv.index("--fault"):argv.index("--fault") + 2]
    plant(fault)
    return control.harness.main(argv)


if __name__ == "__main__":
    sys.exit(main())
