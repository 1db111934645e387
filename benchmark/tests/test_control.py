"""The control and each planted fault come out `correct: false`, and the
sound program `correct: true`, at rehearsal size on the CPU (the chip
readings at the cells' own size are in PERF.md)."""

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


@pytest.mark.parametrize("fault,workload,correct,number", CASES)
def test_fault_decides_correct(fault, workload, correct, number):
    script = os.path.join(HERE, "control.py" if fault else "../run.py")
    cmd = [sys.executable, script, "--workload", workload, "--seed",
           "2147483659", "--seconds", "2", "--trace", "0", "--rehearse"]
    if fault:
        cmd += ["--fault", fault]
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
