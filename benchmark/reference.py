"""The plain reference: what the sink has to hold, worked out from the seed.

Independent of the program: it imports nothing of `etl_tpu` and takes
nothing the program made. The same operations on the same data give the
same answers — every row of every transaction that the slot's reported
flush position has passed is in the sink, once or more (at-least-once), with
the values and the WAL coordinates the source gave it.

All comparisons are exact, so every limit is 0.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"missing_rows": 0, "wrong_rows": 0, "unknown_rows": 0,
          "misattributed_rows": 0}


def verify(ref_cols, first_aid: int, need: list, sent: list, got: dict,
           coords=None) -> dict:
    """Compare what a sink received with the reference.

    `ref_cols` = (aid, bid, abalance) of the source's whole stream, row 0
    being `first_aid`. `sent` and `need` are lists of [lo, hi) row ranges:
    the rows the source sent at all, and those the slot's flush position
    has passed, which MUST be in the sink. `got` holds the sink's columns
    as int64 arrays `aid`, `bid`, `abalance`, the count `bad_text_rows` of
    rows whose filler or change label was not the source's, and optionally
    `commit_lsn` / `tx_ordinal` per row, which are then held to `coords` =
    (commit_lsn, tx_ordinal) of every source row.
    """
    n = len(ref_cols[0])
    was_sent = np.zeros(n, dtype=bool)
    for lo, hi in sent:
        was_sent[lo:hi] = True
    aid = np.asarray(got["aid"], dtype=np.int64)
    idx = aid - first_aid
    known = (idx >= 0) & (idx < n)
    known[known] = was_sent[idx[known]]
    idx_k = idx[known]
    seen = np.bincount(idx_k, minlength=n)
    wrong = (np.asarray(got["bid"], dtype=np.int64)[known]
             != ref_cols[1][idx_k]) \
        | (np.asarray(got["abalance"], dtype=np.int64)[known]
           != ref_cols[2][idx_k])
    out = {
        "missing_rows": int(sum((seen[lo:hi] == 0).sum() for lo, hi in need)),
        "wrong_rows": int(wrong.sum()) + int(got.get("bad_text_rows", 0)),
        "unknown_rows": int((~known).sum()),
        "misattributed_rows": 0,
    }
    if coords is not None and "commit_lsn" in got:
        out["misattributed_rows"] = int((
            (np.asarray(got["commit_lsn"], dtype=np.int64)[known]
             != coords[0][idx_k])
            | (np.asarray(got["tx_ordinal"], dtype=np.int64)[known]
               != coords[1][idx_k])).sum())
    info = {"rows_in_sink": int(len(aid)),
            "rows_required": int(sum(hi - lo for lo, hi in need)),
            "duplicate_rows": int((seen > 1).sum())}
    return {"numbers": out, "info": info}


def judge(numbers: dict) -> tuple:
    """(correct, [[name, number, limit], ...]) — each number beside its
    limit, in the order they are printed."""
    table = [[k, numbers[k], LIMITS[k]] for k in LIMITS if k in numbers]
    extra = [[k, v, 0] for k, v in numbers.items() if k not in LIMITS]
    table += extra
    return all(v <= lim for _, v, lim in table), table
