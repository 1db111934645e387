"""Share of the traced slice covered by the union of the program's own spans
(`etl_tpu/telemetry/spans.py`'s ring, read directly: same process, same
clock as `slice_ns`), in percent.

`spans`: the span names to unite (all of the ring's when absent);
`thread: "loop"`: only records made by the thread that recorded
`loop.select_wait` (the event loop's; in the harness the main thread);
`complement: true`: 100 minus the share — the coverage test. None where the
program has no recorder (the parent of the PR that added it) or none of the
named spans fell in the slice."""

import numpy as np

import trace as trace_mod


def slice_records(ctx: dict):
    """The ring's records overlapping the traced slice, or None."""
    if ctx.get("slice_ns") is None:
        return None
    try:
        from etl_tpu.telemetry import spans
    except ImportError:
        return None
    rec = spans.snapshot(*ctx["slice_ns"])
    return rec if len(rec["name"]) else None


def read(ctx: dict, params: dict):
    rec = slice_records(ctx)
    if rec is None:
        return None
    keep = np.isin(rec["name"], params["spans"]) if "spans" in params \
        else np.ones(len(rec["name"]), dtype=bool)
    if params.get("thread") == "loop":
        loop = rec["thread"][rec["name"] == "loop.select_wait"]
        if not len(loop):
            return None
        idents, counts = np.unique(loop, return_counts=True)
        keep &= rec["thread"] == idents[counts.argmax()]
    if not keep.any():
        return None
    lo, hi = ctx["slice_ns"]
    share = 100.0 * trace_mod.span_busy_share(
        np.stack([rec["t0_ns"][keep], rec["t1_ns"][keep]], axis=1), lo, hi)
    return 100.0 - share if params.get("complement") else share
