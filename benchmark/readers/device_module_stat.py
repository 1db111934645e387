"""Device time of the named device programs, from the slice's own trace file
(the profiler session spans the slice and nothing else, so medians and shares
need no clock shift): over the `XLA Modules` events whose name starts with
`prefix`, `stat: "p50_us"` is their median duration in microseconds and
`stat: "share_pct"` their share of all modules' device time. None where the
trace holds no device plane (a CPU rehearsal) or no such module (a program
whose modules are all `jit_fn`)."""

import functools
import os

import numpy as np

import trace as trace_mod

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_trace")


@functools.lru_cache(maxsize=2)
def modules_of(path: str) -> tuple:
    """((name, duration_ns), ...) over every device plane of one trace."""
    xplane = trace_mod.read_xplane(path)
    return tuple((name, b - a) for dev in xplane["devices"].values()
                 for name, a, b in dev["modules"])


def read(ctx: dict, params: dict):
    try:
        path = ctx.get("xplane_path") or trace_mod.newest_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    modules = modules_of(path)
    mine = np.array([d for name, d in modules
                     if name.startswith(params["prefix"])], dtype=np.float64)
    if not len(mine):
        return None
    if params["stat"] == "share_pct":
        return 100.0 * mine.sum() / sum(d for _, d in modules)
    return float(np.median(mine)) / 1e3
