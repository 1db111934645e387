"""The decode programs' share of the memory-bandwidth roofline: the least
time the chip could take for the rows routed to the device inside the traced
slice, over the decode programs' summed device time in the trace, percent.
Bandwidth-bound: the programs do a few integer operations per byte."""

import roofline


def read(ctx: dict, params: dict):
    tr, sl = ctx["trace"], ctx["slice"]
    if tr is None or not sl or tr["program_s"] <= 0:
        return None
    rows = sum(sl.get(n, 0) for n in params["rows"])
    if rows <= 0:
        return None
    egress = ctx["config"]["destination"]["type"] != "null"
    config = ctx["config"]
    tables = config["tables"] if "tables" in config else [config["table"]]
    nbytes = roofline.decode_bytes(
        roofline.mean_columns(tables, ctx["report"].get("table_event_share")),
        rows,
        ctx["report"]["payload_bytes_per_row"], egress)
    least_s = nbytes / roofline.peak(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["program_s"]
