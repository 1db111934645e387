"""The readers of the program's own spans (`readers/program_span_busy_pct`,
`program_span_stat`, `device_module_stat`): on a hand-made ring, on the
small trace recorded on the chip (`data/paced_tail.xplane.pb`, whose one
module is still named `jit_fn`: the prefix is a parameter), and in a CPU
rehearsal of every cell, where each new metric has to print."""

import itertools
import json
import os
import subprocess
import sys
import threading

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

from etl_tpu.telemetry import spans  # noqa: E402
from readers import (device_module_stat, program_span_busy_pct,  # noqa: E402
                     program_span_stat)

XPLANE = os.path.join(HERE, "data", "paced_tail.xplane.pb")
MS = 1_000_000
CTX = {"slice_ns": (100 * MS, 200 * MS)}


@pytest.fixture
def ring(monkeypatch):
    """A hand-made ring: the loop thread (this one) waits in its select
    for 40 of the slice's 100 ms and works for 35 in two spans that
    overlap by 5; a worker thread packs meanwhile; three flushes."""
    monkeypatch.setattr(spans, "_ring", [None] * spans.CAPACITY)
    monkeypatch.setattr(spans, "_seq", itertools.count())
    spans.record("loop.select_wait", 90 * MS, 120 * MS)  # 20 inside
    spans.record("loop.select_wait", 160 * MS, 180 * MS)
    spans.record("intake.drain", 120 * MS, 140 * MS)
    spans.record("apply.frame_walk", 135 * MS, 155 * MS)
    worker = threading.Thread(
        target=lambda: spans.record("decode.pack", 100 * MS, 200 * MS))
    worker.start()
    worker.join()
    for flush_id, (t0, t1) in enumerate(
            [(101, 103), (110, 116), (150, 151)], start=1):
        # two batches in each flush: two records, one flush
        for batch_id in (10 * flush_id, 10 * flush_id + 1):
            spans.record("flush.fill", t0 * MS, t1 * MS, flush_id=flush_id,
                         parent=batch_id)
    spans.record("flush.fill", 10 * MS, 95 * MS, flush_id=9)  # before
    return spans


def test_busy_share_is_the_union_clipped_to_the_slice(ring):
    read = program_span_busy_pct.read
    assert read(CTX, {"spans": ["intake.drain"]}) == pytest.approx(20.0)
    assert read(CTX, {"spans": ["intake.drain", "apply.frame_walk"]}) \
        == pytest.approx(35.0)
    assert read(CTX, {"spans": ["loop.select_wait"]}) == pytest.approx(40.0)
    assert read(CTX, {"spans": ["decode.pack"]}) == pytest.approx(100.0)


def test_loop_thread_filter_and_complement(ring):
    read = program_span_busy_pct.read
    work = ["loop.select_wait", "intake.drain", "apply.frame_walk",
            "decode.pack"]
    # every thread: the worker's pack covers the slice
    assert read(CTX, {"spans": work, "complement": True}) \
        == pytest.approx(0.0)
    # the loop thread alone: 20 + 35 + 20 of 100 ms spanned
    assert read(CTX, {"spans": work, "thread": "loop",
                      "complement": True}) == pytest.approx(25.0)
    # no list: every name the loop thread recorded, flush.fill included
    assert read(CTX, {"thread": "loop"}) == pytest.approx(75.0)


@pytest.mark.parametrize("params", [
    {"spans": ["copy.read_wait"]},
    {"spans": ["decode.pack"], "thread": "loop"},
])
def test_busy_share_of_an_absent_span_is_none_not_zero(ring, params):
    assert program_span_busy_pct.read(CTX, params) is None


def test_busy_share_without_a_loop_thread_is_none(monkeypatch):
    monkeypatch.setattr(spans, "_ring", [None] * spans.CAPACITY)
    spans.record("intake.drain", 120 * MS, 140 * MS)
    assert program_span_busy_pct.read(
        CTX, {"spans": ["intake.drain"], "thread": "loop"}) is None


@pytest.mark.parametrize("ctx", [{}, {"slice_ns": None}])
def test_no_slice_reads_none(ring, ctx):
    assert program_span_busy_pct.read(ctx, {"spans": ["intake.drain"]}) \
        is None
    assert program_span_stat.read(
        ctx, {"span": "flush.fill", "stat": "p50"}) is None


def test_a_program_without_the_recorder_reads_none(monkeypatch):
    import etl_tpu.telemetry

    monkeypatch.delattr(etl_tpu.telemetry, "spans")
    monkeypatch.setitem(sys.modules, "etl_tpu.telemetry.spans", None)
    assert program_span_busy_pct.read(CTX, {"spans": ["intake.drain"]}) \
        is None
    assert program_span_stat.read(
        CTX, {"span": "flush.fill", "stat": "p50"}) is None


@pytest.mark.parametrize("stat,want", [("p50", 2.0), ("p95", 6.0),
                                       ("max", 6.0)])
def test_span_stat_counts_a_flush_once(ring, stat, want):
    # durations 2, 6, 1 ms: one per flush_id, not one per record
    assert program_span_stat.read(
        CTX, {"span": "flush.fill", "stat": stat}) == pytest.approx(want)


def test_span_stat_of_records_without_ids(ring):
    assert program_span_stat.read(
        CTX, {"span": "loop.select_wait", "stat": "max"}) \
        == pytest.approx(30.0)  # ended inside the slice; whole duration
    assert program_span_stat.read(
        CTX, {"span": "intake.drain", "stat": "p50"}) == pytest.approx(20.0)


def test_span_stat_of_an_absent_span_is_none(ring):
    assert program_span_stat.read(
        CTX, {"span": "copy.cut", "stat": "p50"}) is None


def test_device_module_stat_on_the_recorded_trace():
    ctx = {"xplane_path": XPLANE}
    p50 = device_module_stat.read(ctx, {"prefix": "jit_fn",
                                        "stat": "p50_us"})
    # one 16,384-row decode program: ~22 us of device time (PERF.md)
    assert 10.0 < p50 < 60.0
    assert device_module_stat.read(
        ctx, {"prefix": "jit_fn", "stat": "share_pct"}) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("stat", ["p50_us", "share_pct"])
def test_device_module_stat_of_an_absent_module_is_none(stat):
    assert device_module_stat.read(
        {"xplane_path": XPLANE},
        {"prefix": "jit_etl_decode", "stat": stat}) is None


def test_device_module_stat_without_a_trace_is_none(monkeypatch, tmp_path):
    monkeypatch.setattr(device_module_stat, "TRACE_DIR", str(tmp_path))
    assert device_module_stat.read(
        {}, {"prefix": "jit_etl_decode", "stat": "p50_us"}) is None


def new_metrics(workload: str) -> list:
    """(name, reader) of the per-layer metrics `workload` got with the
    recorder: the entries after the last one PR 24 made."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    out = []
    for m in bench["per_layer"][names.index("paced_lag_p95_ms") + 1:]:
        if workload in m["workloads"]:
            with open(os.path.join(BENCH, "metrics",
                                   m["name"] + ".json")) as f:
                out.append((m["name"], json.load(f)["reader"]))
    return out


@pytest.mark.parametrize("workload,on_cpu_count", [
    ("pgbench-s10-null.backlog-drain", 9),
    ("pgbench-s10-null.insert-paced", 6),
    ("pgbench-s10-null.copy-1m", 6),
    ("pgbench-s10-clickhouse.backlog-drain", 9),
])
def test_every_new_metric_prints_in_a_rehearsal(workload, on_cpu_count):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    expected = new_metrics(workload)
    # a CPU rehearsal has no device plane: the device_trace readers are
    # silent there, every other new metric reports a finite number
    on_cpu = [name for name, reader in expected
              if reader != "device_module_stat"]
    # at least the recorder's own (PR 25): later PRs append theirs
    assert len(on_cpu) >= on_cpu_count
    for name in on_cpu:
        value = line["metrics"]["rehearsal." + name]["value"]
        assert value == value and abs(value) != float("inf"), name
    for name, reader in expected:
        if reader == "device_module_stat":
            assert "rehearsal." + name not in line["metrics"]
