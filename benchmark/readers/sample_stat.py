"""A percentile or the maximum of a named list of samples taken in the
window (`stat`: "p50", "p95", "max")."""

import numpy as np


def read(ctx: dict, params: dict):
    values = ctx["samples"].get(params["samples"])
    if values is None or not len(values):
        return None
    arr = np.asarray(values, dtype=np.float64)
    if arr.ndim == 2:  # (perf_counter_ns, value): keep the window's own
        arr = arr[(arr[:, 0] >= ctx["opened_ns"])
                  & (arr[:, 0] <= ctx["closed_ns"]), 1]
        if not len(arr):
            return None
    arr = np.sort(arr[np.isfinite(arr)])
    if not len(arr):
        return None
    stat = params["stat"]
    if stat == "max":
        return float(arr[-1])
    q = float(stat[1:]) / 100.0
    return float(arr[min(len(arr) - 1, int(len(arr) * q))])
