"""A percentile or the maximum (`stat`: "p50", "p95", "max") of the durations,
in milliseconds, of one of the program's own spans (`span`) that ended inside
the traced slice. Records that share a non-zero id count once (`flush.fill`
has one record per batch the flush consumed). None where the program has no
recorder or the span did not occur."""

import numpy as np

from readers.program_span_busy_pct import slice_records


def read(ctx: dict, params: dict):
    rec = slice_records(ctx)
    if rec is None:
        return None
    lo, hi = ctx["slice_ns"]
    keep = (rec["name"] == params["span"]) & (rec["t1_ns"] >= lo) \
        & (rec["t1_ns"] < hi)
    ids = rec["id"][keep]
    ms = (rec["t1_ns"][keep] - rec["t0_ns"][keep]) / 1e6
    if len(ids) and ids.all():
        ms = ms[np.unique(ids, return_index=True)[1]]
    if not len(ms):
        return None
    ms = np.sort(ms)
    stat = params["stat"]
    if stat == "max":
        return float(ms[-1])
    return float(ms[min(len(ms) - 1, int(len(ms) * float(stat[1:]) / 100.0))])
