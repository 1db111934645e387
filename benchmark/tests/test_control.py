"""The control and each planted fault come out `correct: false`, and the
sound program `correct: true`, at rehearsal size on the CPU (the chip
readings at the cells' own size are in PERF.md): on the two pgbench
configurations, and on the fixture deployment of `data/` — two tables,
every type, updates and deletes — through --config-file / --traffic-file
in each traffic kind into each destination."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
NULL_DRAIN = "pgbench-s10-null.backlog-drain"
CASES = [
    (None, NULL_DRAIN, True, None),
    ("ack_and_drop", NULL_DRAIN, False, "missing_rows"),
    ("ack_and_drop", "pgbench-s10-clickhouse.backlog-drain", False,
     "missing_rows"),
    ("ack_and_drop", "pgbench-s10-null.copy-1m", False, "missing_rows"),
    ("half_batch", "pgbench-s10-null.insert-paced", False, "missing_rows"),
    ("altered_value", NULL_DRAIN, False, "wrong_rows"),
    ("altered_value", "pgbench-s10-clickhouse.backlog-drain", False,
     "wrong_rows"),
]


FIXTURE = [  # (fault, destination, traffic kind, correct, number)
    (None, "null", "backlog", True, None),
    (None, "null", "paced", True, None),
    (None, "null", "copy", True, None),
    (None, "clickhouse", "backlog", True, None),
    (None, "clickhouse", "paced", True, None),
    (None, "clickhouse", "copy", True, None),
    ("update_dropped", "null", "backlog", False, "state_mismatch_rows"),
    ("delete_as_upsert", "null", "backlog", False, "wrong_rows"),
    ("updates_swapped", "null", "backlog", False, "state_mismatch_rows"),
    ("numeric_digit", "null", "backlog", False, "wrong_rows"),
    ("null_to_empty", "null", "paced", False, "wrong_rows"),
    ("other_table", "null", "paced", False, "misattributed_rows"),
    ("ack_and_drop", "clickhouse", "backlog", False, "missing_rows"),
]
# the cell whose metrics a fixture run of each kind reports
CELL_OF = {"backlog": "backlog-drain", "paced": "insert-paced",
           "copy": "copy-1m"}


@pytest.mark.parametrize("fault,destination,kind,correct,number", FIXTURE)
def test_fixture_deployment(fault, destination, kind, correct, number):
    data = os.path.join(HERE, "data")
    run([*(["--fault", fault] if fault else []),
         "--config-file", os.path.join(data, f"fixture-{destination}.json"),
         "--traffic-file", os.path.join(data, f"fixture-{kind}.json")],
        fault, "pgbench-s10-null." + CELL_OF[kind], correct, number)


@pytest.mark.parametrize("fault,workload,correct,number", CASES)
def test_fault_decides_correct(fault, workload, correct, number):
    run(["--fault", fault] if fault else [], fault, workload, correct, number)


def run(more, fault, workload, correct, number):
    script = os.path.join(HERE, "control.py" if fault else "../run.py")
    cmd = [sys.executable, script, "--workload", workload, "--seed",
           "2147483659", "--seconds", "2", "--trace", "0", "--rehearse",
           *more]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct, line["checks"]
    if number:
        assert line["checks"][number]["value"] > line["checks"][number]["limit"]
    # each number compared is printed beside its limit, last on stderr
    assert "check missing_rows:" in out.stderr
    assert list(line)[-1] == "checks"
