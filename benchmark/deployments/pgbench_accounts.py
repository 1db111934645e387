"""pgbench_accounts from a seed: the deployment of the two pgbench
configurations (`pgbench -i -s 10`), and its insert-only change stream.

`accounts_columns` is copied from `chip_smoke.py` (PR 21), where it was
judged sound. Nothing here imports the program, JAX, `run.py` or
`source.py`.

The mix's parameters are the traffic file's own keys: every transaction
inserts `transaction_rows` rows, except that every
`bulk_every_transactions`-th inserts `bulk_rows` — a bulk insert among the
small ones, which seals at the device-routed size. A backlog holds
`backlog_events_per_second` events for every second of warm-up and window
and one more; a paced mix one transaction per due time. Transaction k
inserts the aids after those of transaction k-1, from `rows` + 1 on.
"""

from __future__ import annotations

import numpy as np

from oplog import INSERT, Col, Stream, TableEvents, TxLayout, tables_of

ACCOUNTS_PER_BRANCH = 100_000  # pgbench's naccounts: bid = (aid-1)/100000+1


def accounts_columns(seed: int, n: int, first_aid: int = 1):
    """`n` pgbench_accounts rows from `first_aid` on, as int64 columns:
    aid sequential, bid by pgbench's rule, abalance uniform in +-10^9
    (pgbench initialises 0; listed under `assumed` in the configurations)."""
    rng = np.random.default_rng([seed, first_aid])
    aid = np.arange(first_aid, first_aid + n, dtype=np.int64)
    bid = (aid - 1) // ACCOUNTS_PER_BRANCH + 1
    abalance = rng.integers(-10**9, 10**9, size=n, dtype=np.int64)
    return aid, bid, abalance


def _rows(seed: int, n: int, first_aid: int) -> list:
    # filler char(84) is left blank: the renderer pads it
    return [*(Col(c) for c in accounts_columns(seed, n, first_aid)), Col(b"")]


def transaction_rows(traffic: dict, seconds: float) -> np.ndarray:
    """Rows of each transaction the mix plays, from its parameters alone."""
    tx_rows = int(traffic["transaction_rows"])
    every = int(traffic.get("bulk_every_transactions", 0))
    bulk = int(traffic.get("bulk_rows", 0)) if every else 0
    if traffic["kind"] == "backlog":
        events = float(traffic["backlog_events_per_second"]) * (
            float(traffic["warmup_seconds"]) + float(seconds) + 1.0)
        mean = tx_rows + (bulk - tx_rows) / every if every else tx_rows
        n = int(-(-events // mean))
    else:
        rate = float(traffic["transactions_per_second"])
        n = round(float(traffic["warmup_seconds"]) * rate) \
            + round(float(seconds) * rate)
    rows = np.full(n, tx_rows, dtype=np.int64)
    if every:
        rows[every - 1::every] = bulk
    return rows


def snapshot(config: dict, traffic: dict, seed: int) -> dict:
    """The copy mix copies the table's `rows` rows; in the CDC mixes the
    table is empty at the snapshot (`assumed.cdc_snapshot`)."""
    n = int(config["rows"]) if traffic["kind"] == "copy" else 0
    return {int(tables_of(config)[0]["id"]): _rows(seed, n, 1)}


def stream(config: dict, traffic: dict, seed: int, seconds: float):
    if traffic["kind"] not in ("backlog", "paced"):
        return None
    layout = TxLayout.build(transaction_rows(traffic, seconds))
    n = int(layout.rows.sum())
    return Stream(layout, np.zeros(n, dtype=np.uint8),
                  np.full(n, INSERT, dtype=np.uint8),
                  {0: TableEvents(_rows(seed, n, int(config["rows"]) + 1))})
