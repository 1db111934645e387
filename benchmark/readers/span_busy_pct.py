"""Share of the traced slice during which a named host span was open (the
union of its intervals, so overlapping calls count once), in percent."""

import trace as trace_mod


def read(ctx: dict, params: dict):
    spans = ctx["spans"].get(params["span"])
    if ctx["slice_ns"] is None or spans is None or not len(spans):
        return None
    lo, hi = ctx["slice_ns"]
    return 100.0 * trace_mod.span_busy_share(spans, lo, hi)
