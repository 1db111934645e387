"""From a profiler trace (`*.xplane.pb`) and the harness's host spans to the
numbers the benchmark reports: device busy and idle time, the device
operations that took most time, the decode programs' summed device time, and
the device's idle time attributed to what the host was doing.

Kept as code with the benchmark and checked on a small recorded trace
(`tests/test_trace.py`), so every PR computes the same number the same way.
Reads the trace with nothing but JAX (`jax.profiler.ProfileData`).

Clocks: the trace has its own; the harness stamps host spans with
`time.perf_counter_ns()`. One `TraceAnnotation(MARKER)` entered right after
the trace starts is stamped on both, and that offset carries device
intervals onto the harness's clock.
"""

from __future__ import annotations

import glob
import os

import numpy as np

MARKER = "bench_marker"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BIN_NS = 50_000  # resolution of the idle-gap attribution
UNSPANNED = "outside_the_benchmark_s_spans"


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_length(intervals: np.ndarray) -> float:
    """Total length of the union of [start, end) rows."""
    return float(sum(b - a for a, b in merged(intervals)))


def merged(intervals: np.ndarray) -> list:
    if len(intervals) == 0:
        return []
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [[iv[0, 0], iv[0, 1]]]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def short_name(name: str) -> str:
    """An op's event carries its whole HLO line; keep the instruction."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def read_xplane(path: str) -> dict:
    """{"devices": {plane name: {"ops": [(name, start_ns, end_ns)],
    "modules": [...]}}, "marker_ns": trace time of the marker or None}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    marker = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            dev = {"ops": [], "modules": []}
            for key, name in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                if name in lines:
                    dev[key] = [(short_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in lines[name].events]
            devices[plane.name] = dev
        elif marker is None and plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARKER:
                        marker = e.start_ns
                        break
                if marker is not None:
                    break
    return {"devices": devices, "marker_ns": marker}


def reduce_trace(xplane: dict, marker_host_ns: int, slice_ns: tuple,
                 host_spans: dict, priorities: dict) -> dict:
    """The traced slice's device numbers, on the harness's clock.

    `slice_ns` is (start, end) of the traced window and `host_spans`
    {span name: int64[n,2] start/end}, both in perf_counter_ns;
    `priorities` ranks span names for the attribution (higher wins where
    spans overlap: the innermost layer should rank highest).
    """
    lo, hi = slice_ns
    if xplane["marker_ns"] is None:
        raise ValueError("the trace holds no marker event; the clocks of "
                         "the trace and the harness cannot be aligned")
    shift = marker_host_ns - xplane["marker_ns"]
    busy_s, per_device_busy = [], []
    op_seconds: dict = {}
    module_s = 0.0
    for dev in xplane["devices"].values():
        ops = np.array([(a + shift, b + shift) for _, a, b in dev["ops"]],
                       dtype=np.float64).reshape(-1, 2)
        ops_in = clip(ops, lo, hi)
        per_device_busy.append(merged(ops_in))
        busy_s.append(union_length(ops_in) / 1e9)
        for (name, a, b) in dev["ops"]:
            a, b = max(a + shift, lo), min(b + shift, hi)
            if b > a:
                op_seconds[name] = op_seconds.get(name, 0.0) + (b - a) / 1e9
        mods = np.array([(a + shift, b + shift) for _, a, b in dev["modules"]],
                        dtype=np.float64).reshape(-1, 2)
        module_s += union_length(clip(mods, lo, hi)) / 1e9
    n_dev = max(1, len(xplane["devices"]))
    out = {
        "window_s": (hi - lo) / 1e9,
        "busy_s": float(sum(busy_s) / n_dev),
        "devices": len(xplane["devices"]),
        "device_ops": sorted(([k, v] for k, v in op_seconds.items()),
                             key=lambda kv: -kv[1])[:10],
        # the decode programs are the only programs this process runs on
        # the device inside the slice, so their time is the modules' time
        # (the ops' union where the trace has no module line)
        "program_s": module_s / n_dev if module_s else
        float(sum(busy_s) / n_dev),
    }
    out["idle_gaps"] = idle_gaps(per_device_busy[0] if per_device_busy
                                 else [], lo, hi, host_spans, priorities)
    return out


def idle_gaps(busy: list, lo: float, hi: float, host_spans: dict,
              priorities: dict) -> list:
    """The first device's idle time inside [lo, hi), split by the host
    span that was open (highest priority wins), longest first."""
    n = max(1, int((hi - lo) // BIN_NS))
    idle = np.ones(n, dtype=bool)

    def paint(target: np.ndarray, intervals) -> None:
        for a, b in intervals:
            i, j = int((a - lo) // BIN_NS), int(-((lo - b) // BIN_NS))
            target[max(0, i):min(n, j)] = True

    device = np.zeros(n, dtype=bool)
    paint(device, busy)
    idle &= ~device
    owner = np.full(n, -1, dtype=np.int64)
    names = sorted(host_spans, key=lambda s: priorities.get(s, 0))
    for rank, name in enumerate(names):  # ascending: later overwrites
        mask = np.zeros(n, dtype=bool)
        paint(mask, clip(np.asarray(host_spans[name], dtype=np.float64)
                         .reshape(-1, 2), lo, hi))
        owner[mask] = rank
    out = [[UNSPANNED, float((idle & (owner < 0)).sum() * BIN_NS / 1e9)]]
    for rank, name in enumerate(names):
        out.append([name, float((idle & (owner == rank)).sum()
                                * BIN_NS / 1e9)])
    return sorted((g for g in out if g[1] > 0), key=lambda g: -g[1])[:10]


def span_busy_share(intervals, lo: float, hi: float) -> float:
    """Share of [lo, hi) covered by the union of a span's intervals."""
    iv = clip(np.asarray(intervals, dtype=np.float64).reshape(-1, 2), lo, hi)
    return union_length(iv) / (hi - lo)
