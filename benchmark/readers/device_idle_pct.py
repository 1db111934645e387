"""Share of the traced slice in which no operation ran on the device, from
the profiler's trace, in percent."""


def read(ctx: dict, params: dict):
    tr = ctx["trace"]
    if tr is None or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
