"""scale * sum(num) / sum(den) over counter deltas.

`scope` is "window" (open to close, the default) or "slice" (the traced
slice). Names: the program's counters (`etl_*`), histogram sums and counts
(`hist_sum:<name>`, `hist_count:<name>`) and the harness's own readings
(`window.*`, `source.*`, `sink.*`, `setup.*`, `harness.*`, `const.one`)."""


def read(ctx: dict, params: dict):
    scope = ctx.get(params.get("scope", "window"))
    if not scope:
        return None

    def total(names):
        names = [names] if isinstance(names, str) else names
        if any(scope.get(n) is None for n in names):
            return None
        return sum(scope[n] for n in names)

    num, den = total(params["num"]), total(params.get("den", "const.one"))
    if num is None or not den:
        return None
    return float(params.get("scale", 1.0)) * num / den
