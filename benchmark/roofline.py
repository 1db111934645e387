"""The table of peaks and the byte count of a decode, kept with the
benchmark so that no PR that claims a gain can change the yardstick.

The decode is bandwidth-bound: it reads each routed row's pgoutput payload
once and writes each typed column once (and, where the destination takes
device-rendered text, that text once). The count depends on the schema and
the payload lengths only, so it reads the same whatever program implements
the decode (XLA or Pallas, any bucket or padding)."""

import json
import os

# bytes of the typed value a device-decoded column kind is written as;
# a column type that is not here (text, bpchar, numeric, ...) stays on the
# host and adds nothing
TYPED_BYTES = {"bool": 1, "int2": 2, "int4": 4, "int8": 8, "float4": 4,
               "float8": 8, "date": 4, "time": 8, "timestamp": 8,
               "timestamptz": 8, "oid": 4}
# what a pgoutput INSERT carries besides the column texts: 'I' relid 'N'
# ncols, and 't' + int32 length per column
MESSAGE_OVERHEAD = 8
COLUMN_OVERHEAD = 5


def peak(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peak recorded for device kind {device_kind!r}; "
                       f"add it to peaks.json with its source")
    return peaks[device_kind]


def mean_columns(tables: list, share: "dict | None") -> list:
    """The columns of the rows a decode sees: one table's own, or those of
    several tables weighted by each table's share of the stream's events
    (`share`: {table name: share}, the source's report) — the count below
    is linear in the columns, so a weighted column counts its share."""
    if len(tables) == 1 or not share:
        return tables[0]["columns"]
    return [{**c, "weight": float(share.get(t["name"], 0.0))}
            for t in tables for c in t["columns"]]


def decode_bytes(columns: list, rows: float, payload_bytes_per_row: float,
                 egress: bool) -> float:
    """Bytes the chip has to move at the least for `rows` routed rows of
    the schema `columns` (`mean_columns` gives a mix of tables')."""
    def w(c):
        return c.get("weight", 1.0)

    typed = sum(w(c) * TYPED_BYTES.get(c["type"], 0) for c in columns)
    total = payload_bytes_per_row + typed
    if egress:
        host_text = sum(w(c) * c.get("text_bytes", 0) for c in columns
                        if c["type"] not in TYPED_BYTES)
        total += max(0.0, payload_bytes_per_row - MESSAGE_OVERHEAD
                     - COLUMN_OVERHEAD * sum(w(c) for c in columns)
                     - host_text)
    return rows * total
