"""TPC-C from a seed: the deployment of `tpcc-w4-null` — the eight published
tables of go-tpc's schema (`history` has no primary key and is not
published) as the specification loads them (TPC-C v5.11 §4.3.3), and the
standard transaction mix (§5.2.3) as the change stream its WAL holds.

Nothing here imports the program, JAX, `run.py` or `source.py`. Everything
is made with numpy from `(config, traffic, seed, seconds)` alone; the
sizes are the configuration's top-level keys (`warehouses`,
`districts_per_warehouse`, `customers_per_district`, `orders_per_district`,
`undelivered_orders_per_district`, `items`), the mix's parameters the
traffic file's `generator` object.

The log is a TPC-C history, not rows drawn at random: the generator keeps
what a driver's database keeps — `d_next_o_id`, each district's queue of
undelivered orders, `s_quantity` with its +91 wrap, `s_ytd` / `s_order_cnt`
/ `s_remote_cnt`, `w_ytd`, `d_ytd`, `c_balance` / `c_ytd_payment` /
`c_payment_cnt` / `c_delivery_cnt` and a bad-credit customer's `c_data` —
as running sums per key over the drawn transactions, so every new image is
the row the transaction would have written.

What reaches the WAL, in go-tpc's statement order (`assumed` in the
configuration's file):

  new-order  UPDATE district · INSERT orders · INSERT new_order ·
             ol_cnt x UPDATE stock · ol_cnt x INSERT order_line
             (1% roll back and emit nothing)
  payment    UPDATE warehouse · UPDATE district · UPDATE customer
             (the `history` insert is not published)
  delivery   per warehouse, over its districts with an undelivered order:
             DELETE new_order · UPDATE orders · UPDATE order_line ·
             UPDATE customer
  order-status, stock-level   read-only: nothing

and after every `bulk_every_transactions` drawn transactions one INSERT
transaction of `bulk_rows` order_line rows of a warehouse being loaded
beside the published ones (keys above theirs). Updates never change a key
and every table's replica identity is default, so an update carries no old
image (`TableEvents.old` is None there: the harness reads that as "the key
stayed"); a delete carries its key.
"""

from __future__ import annotations

import numpy as np

from oplog import (DELETE, INSERT, UPDATE, Col, Stream, TableEvents,
                   TxLayout, tables_of)

TABLES = ("warehouse", "district", "customer", "new_order", "orders",
          "order_line", "stock", "item")
(WAREHOUSE, DISTRICT, CUSTOMER, NEW_ORDER, ORDERS, ORDER_LINE, STOCK,
 ITEM) = range(8)
NEW_ORDER_TX, PAYMENT_TX, ORDER_STATUS_TX, DELIVERY_TX, STOCK_LEVEL_TX = \
    range(5)
MIX = ("new_order", "payment", "order_status", "delivery", "stock_level")
SYLLABLES = (b"BAR", b"OUGHT", b"ABLE", b"PRI", b"PRES", b"ESE", b"ANTI",
             b"CALLY", b"ATION", b"EING")
LOADED_US = 1_704_067_200_000_000   # 2024-01-01 00:00:00: the load's stamp
STREAM_US = LOADED_US + 86_400_000_000  # the first drawn transaction's
TX_STEP_US = 7_013                  # ... and the step to the next one
MEAN_OL_CNT = 10

_LOADED: dict = {}  # {(seed, sizes): Load} — the last load made, kept for
#                     the stream that is made from it in the same process


# ---------------------------------------------------------------------------
# drawing
# ---------------------------------------------------------------------------


def _rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), tag])


def nurand(rng, a: int, x: int, y: int, c: int, n: int) -> np.ndarray:
    """TPC-C §2.1.6: (((random(0,A) | random(x,y)) + C) % (y-x+1)) + x."""
    return ((rng.integers(0, a + 1, n) | rng.integers(x, y + 1, n)) + c) \
        % (y - x + 1) + x


def _letters(rng, n: int, lo: int, hi: int, first: int = ord("a"),
             span: int = 26) -> np.ndarray:
    """`n` random strings of lo..hi characters, as a numpy S array."""
    chars = rng.integers(first, first + span, (n, hi), dtype=np.uint8)
    if lo < hi:
        chars[np.arange(hi)[None, :]
              >= rng.integers(lo, hi + 1, n)[:, None]] = 0
    return np.ascontiguousarray(chars).view(f"S{hi}").ravel()


def _digits(rng, n: int, width: int) -> np.ndarray:
    return _letters(rng, n, width, width, ord("0"), 10)


def _zip(rng, n: int) -> np.ndarray:
    return np.char.add(_digits(rng, n, 4), b"11111")


def _with_original(rng, data: np.ndarray) -> np.ndarray:
    """§4.3.3.1: a tenth of the rows hold "ORIGINAL" somewhere in the
    field (here: at its head, which any place satisfies)."""
    marked = rng.random(len(data)) < 0.1
    out = data.copy()
    view = out.view(np.uint8).reshape(len(out), -1)
    view[marked, :8] = np.frombuffer(b"ORIGINAL", dtype=np.uint8)
    return out


def last_names() -> np.ndarray:
    """S15[1000]: §4.3.2.3's customer last name of each number 0..999."""
    syl = np.array(SYLLABLES)
    n = np.arange(1000)
    return np.char.add(np.char.add(syl[n // 100], syl[n // 10 % 10]),
                       syl[n % 10])


def sizes_of(config: dict) -> tuple:
    return tuple(int(config[k]) for k in (
        "warehouses", "districts_per_warehouse", "customers_per_district",
        "orders_per_district", "undelivered_orders_per_district", "items"))


# ---------------------------------------------------------------------------
# the load (§4.3.3): what the tables hold when the pipeline starts
# ---------------------------------------------------------------------------


class Load:
    """The loaded tables as named numpy columns (`rows[table][column]`,
    NULL masks under `nulls[table][column]`), sorted by primary key."""

    def __init__(self, config: dict, seed: int):
        W, D, C, O, U, I = self.sizes = sizes_of(config)
        self.rows: dict = {}
        self.nulls: dict = {t: {} for t in TABLES}
        rng = _rng(seed, 1)
        self.rows["item"] = {
            "i_id": np.arange(1, I + 1, dtype=np.int64),
            "i_im_id": rng.integers(1, 10_001, I),
            "i_name": _letters(rng, I, 14, 24),
            "i_price": rng.integers(100, 10_001, I),
            "i_data": _with_original(rng, _letters(rng, I, 26, 50))}
        rng = _rng(seed, 2)
        self.rows["warehouse"] = {
            "w_id": np.arange(1, W + 1, dtype=np.int64),
            "w_name": _letters(rng, W, 6, 10),
            "w_street_1": _letters(rng, W, 10, 20),
            "w_street_2": _letters(rng, W, 10, 20),
            "w_city": _letters(rng, W, 10, 20),
            "w_state": _letters(rng, W, 2, 2, ord("A")),
            "w_zip": _zip(rng, W),
            "w_tax": rng.integers(0, 2001, W),
            "w_ytd": np.full(W, 30_000_000, dtype=np.int64)}
        rng = _rng(seed, 3)
        n = W * D
        self.rows["district"] = {
            "d_id": np.tile(np.arange(1, D + 1, dtype=np.int64), W),
            "d_w_id": np.repeat(np.arange(1, W + 1, dtype=np.int64), D),
            "d_name": _letters(rng, n, 6, 10),
            "d_street_1": _letters(rng, n, 10, 20),
            "d_street_2": _letters(rng, n, 10, 20),
            "d_city": _letters(rng, n, 10, 20),
            "d_state": _letters(rng, n, 2, 2, ord("A")),
            "d_zip": _zip(rng, n),
            "d_tax": rng.integers(0, 2001, n),
            "d_ytd": np.full(n, 30_000_000 // D, dtype=np.int64),
            "d_next_o_id": np.full(n, O + 1, dtype=np.int64)}
        rng = _rng(seed, 4)
        n = W * D * C
        c_id = np.tile(np.arange(1, C + 1, dtype=np.int64), W * D)
        # the first 1,000 customers of a district take each name once, the
        # rest NURand(255, 0, 999) (§4.3.3.1), C_LAST from the seed
        name_no = np.where(c_id <= 1000, (c_id - 1) % 1000, nurand(
            rng, 255, 0, 999, int(rng.integers(0, 256)), n))
        self.rows["customer"] = {
            "c_id": c_id,
            "c_d_id": np.tile(np.repeat(
                np.arange(1, D + 1, dtype=np.int64), C), W),
            "c_w_id": np.repeat(np.arange(1, W + 1, dtype=np.int64), D * C),
            "c_first": _letters(rng, n, 8, 16),
            "c_middle": b"OE",
            "c_last": last_names()[name_no],
            "c_street_1": _letters(rng, n, 10, 20),
            "c_street_2": _letters(rng, n, 10, 20),
            "c_city": _letters(rng, n, 10, 20),
            "c_state": _letters(rng, n, 2, 2, ord("A")),
            "c_zip": _zip(rng, n),
            "c_phone": _digits(rng, n, 16),
            "c_since": np.full(n, LOADED_US, dtype=np.int64),
            "c_credit": np.where(rng.random(n) < 0.1, b"BC", b"GC"),
            "c_credit_lim": np.full(n, 5_000_000, dtype=np.int64),
            "c_discount": rng.integers(0, 5001, n),
            "c_balance": np.full(n, -1000, dtype=np.int64),
            "c_ytd_payment": np.full(n, 1000, dtype=np.int64),
            "c_payment_cnt": np.ones(n, dtype=np.int64),
            "c_delivery_cnt": np.zeros(n, dtype=np.int64),
            "c_data": _letters(rng, n, 300, 500)}
        rng = _rng(seed, 5)
        n = W * I
        self.rows["stock"] = {
            "s_i_id": np.tile(np.arange(1, I + 1, dtype=np.int64), W),
            "s_w_id": np.repeat(np.arange(1, W + 1, dtype=np.int64), I),
            "s_quantity": rng.integers(10, 101, n),
            **{f"s_dist_{k:02d}": _letters(rng, n, 24, 24)
               for k in range(1, 11)},
            "s_ytd": np.zeros(n, dtype=np.int64),
            "s_order_cnt": np.zeros(n, dtype=np.int64),
            "s_remote_cnt": np.zeros(n, dtype=np.int64),
            "s_data": _with_original(rng, _letters(rng, n, 26, 50))}
        rng = _rng(seed, 6)
        n = W * D * O
        o_id = np.tile(np.arange(1, O + 1, dtype=np.int64), W * D)
        delivered = o_id <= O - U
        # each district's orders go to a permutation of its customers
        # (cycled where a rehearsal has more orders than customers)
        o_c_id = np.argsort(rng.random((W * D, O)), axis=1).ravel() % C + 1
        ol_cnt = rng.integers(5, 16, n)
        self.rows["orders"] = {
            "o_id": o_id,
            "o_d_id": np.tile(np.repeat(
                np.arange(1, D + 1, dtype=np.int64), O), W),
            "o_w_id": np.repeat(np.arange(1, W + 1, dtype=np.int64), D * O),
            "o_c_id": o_c_id,
            "o_entry_d": np.full(n, LOADED_US, dtype=np.int64),
            "o_carrier_id": np.where(delivered, rng.integers(1, 11, n), 0),
            "o_ol_cnt": ol_cnt,
            "o_all_local": np.ones(n, dtype=np.int64)}
        self.nulls["orders"]["o_carrier_id"] = ~delivered
        orders = self.rows["orders"]
        open_orders = ~delivered
        self.rows["new_order"] = {
            "no_o_id": o_id[open_orders],
            "no_d_id": orders["o_d_id"][open_orders],
            "no_w_id": orders["o_w_id"][open_orders]}
        lines = int(ol_cnt.sum())
        of = np.repeat(np.arange(n), ol_cnt)
        self.line_start = np.concatenate(([0], np.cumsum(ol_cnt)))
        line_delivered = delivered[of]
        self.rows["order_line"] = {
            "ol_o_id": o_id[of],
            "ol_d_id": orders["o_d_id"][of],
            "ol_w_id": orders["o_w_id"][of],
            "ol_number": np.arange(lines) - self.line_start[of] + 1,
            "ol_i_id": rng.integers(1, I + 1, lines),
            "ol_supply_w_id": orders["o_w_id"][of],
            "ol_delivery_d": np.where(line_delivered, LOADED_US, 0),
            "ol_quantity": np.full(lines, 5, dtype=np.int64),
            "ol_amount": np.where(line_delivered, 0,
                                  rng.integers(1, 1_000_000, lines)),
            "ol_dist_info": _letters(rng, lines, 24, 24)}
        self.nulls["order_line"]["ol_delivery_d"] = ~line_delivered

    def cols(self, table: dict) -> list:
        """The table's loaded rows as Cols, in the configuration's column
        order."""
        return _cols(table, self.rows[_short(table)],
                     self.nulls[_short(table)])


def _short(table: dict) -> str:
    return table["name"].split(".")[-1]


def _cols(table: dict, values: dict, nulls: "dict | None" = None) -> list:
    nulls = nulls or {}
    return [Col(values[c["name"]], nulls.get(c["name"]))
            for c in table["columns"]]


def load(config: dict, seed: int) -> Load:
    key = (int(seed), sizes_of(config))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = Load(config, seed)
    return _LOADED[key]


def snapshot(config: dict, traffic: dict, seed: int) -> dict:
    loaded = load(config, seed)
    return {int(t["id"]): loaded.cols(t) for t in tables_of(config)}


# ---------------------------------------------------------------------------
# the mix (§5.2.3) as an operation log
# ---------------------------------------------------------------------------


def drawn_transactions(traffic: dict, seconds: float) -> int:
    """How many transactions are drawn: the same for every seed. The
    backlog holds `backlog_events_per_second` events for every second of
    warm-up and window and one more, by the mix's expected events a drawn
    transaction (an order has 10 lines on average, a delivery ten
    orders' worth)."""
    g = traffic["generator"]
    mix = g["mix"]
    lines = MEAN_OL_CNT
    per_drawn = (mix["new_order"] * (1 - g["new_order_rollback"])
                 * (3 + 2 * lines) + mix["payment"] * 3
                 + mix["delivery"] * 10 * (3 + lines)) / sum(mix.values())
    every = int(g.get("bulk_every_transactions", 0))
    if every:
        per_drawn += int(g["bulk_rows"]) / every
    events = float(traffic["backlog_events_per_second"]) * (
        float(traffic["warmup_seconds"]) + float(seconds) + 1.0)
    return int(-(-events // per_drawn))


def _running(keys: np.ndarray, *deltas) -> list:
    """Per event, the sum of each of `deltas` over the events of the same
    key up to and including it, in the order given."""
    order = np.argsort(keys, kind="stable")
    k = keys[order]
    first = np.flatnonzero(np.concatenate(([True], k[1:] != k[:-1])))
    counts = np.diff(np.concatenate((first, [len(k)])))
    out = []
    for delta in deltas:
        d = np.asarray(delta, dtype=np.int64)[order]
        c = np.cumsum(d)
        mine = np.empty(len(k), dtype=np.int64)
        mine[order] = c - np.repeat(c[first] - d[first], counts)
        out.append(mine)
    return out


def _other_warehouse(rng, w: np.ndarray, n_warehouses: int) -> np.ndarray:
    return (w - 1 + rng.integers(1, max(2, n_warehouses), len(w))) \
        % n_warehouses + 1


def _delivered(n_deliveries: int, open_before: np.ndarray) -> np.ndarray:
    """bool[k]: which of one district's k deliveries find an undelivered
    order, where `open_before[j]` orders had been entered and not loaded
    as delivered before the j-th: a queue served at most once a delivery
    (served_j = min(served_j-1 + 1, open_before[j]))."""
    j = np.arange(1, n_deliveries + 1)
    served = j + np.minimum(0, np.minimum.accumulate(open_before - j))
    return np.diff(np.concatenate(([0], served))) > 0


def _merged(parts: list) -> dict:
    """Several sources of one table's events — dicts of equal-length
    arrays with `pos`, the event's place in the stream — as one, in
    stream order."""
    parts = [p for p in parts if len(p["pos"])]
    if not parts:
        return {}
    if len(parts) == 1:
        return parts[0]
    order = np.argsort(np.concatenate([p["pos"] for p in parts]),
                       kind="stable")
    return {k: np.concatenate([np.asarray(p[k]) for p in parts])[order]
            for k in parts[0]}


def _rows_after(loaded_rows: dict, row: np.ndarray, **added) -> dict:
    """The loaded rows `row` of one table as they read after each event:
    every column as loaded, the columns of `added` with that event's
    running sum on top."""
    values = {k: v if isinstance(v, bytes) else v[row]
              for k, v in loaded_rows.items()}
    for name, running in added.items():
        values[name] = values[name] + running
    return values


def stream(config: dict, traffic: dict, seed: int, seconds: float):
    if traffic["kind"] == "copy":
        return None
    if traffic["kind"] != "backlog":
        raise SystemExit("the TPC-C generator plays a backlog: a paced mix "
                         "needs a transaction for every due time, and a "
                         "drawn transaction may be read-only")
    tables = tables_of(config)
    loaded = load(config, seed)
    W, D, C, O, U, I = loaded.sizes
    g = traffic["generator"]
    N = drawn_transactions(traffic, seconds)
    rng = _rng(seed, 10)
    weights = np.array([g["mix"][k] for k in MIX], dtype=np.float64)
    kind = rng.choice(len(MIX), N, p=weights / weights.sum())
    tx_w = rng.integers(1, W + 1, N)
    tx_d = rng.integers(1, D + 1, N)
    tx_us = STREAM_US + TX_STEP_US * np.arange(N, dtype=np.int64)
    c_run = int(rng.integers(0, int(g["nurand_customer_a"]) + 1))
    i_run = int(rng.integers(0, int(g["nurand_item_a"]) + 1))

    # -- new-order -----------------------------------------------------------
    no = np.flatnonzero(kind == NEW_ORDER_TX)
    no = no[rng.random(len(no)) >= float(g["new_order_rollback"])]
    no_w, no_d = tx_w[no], tx_d[no]
    no_group = (no_w - 1) * D + no_d - 1
    by_group = np.argsort(no_group, kind="stable")
    group_start = np.searchsorted(no_group[by_group], np.arange(W * D + 1))
    rank = np.empty(len(no), dtype=np.int64)
    rank[by_group] = np.arange(len(no)) - group_start[no_group[by_group]]
    no_o = O + 1 + rank
    no_c = nurand(rng, int(g["nurand_customer_a"]), 1, C, c_run, len(no))
    no_cnt = rng.integers(5, 16, len(no))
    no_line0 = np.concatenate(([0], np.cumsum(no_cnt)))
    n_lines = int(no_line0[-1])
    of = np.repeat(np.arange(len(no)), no_cnt)
    number = np.arange(n_lines) - no_line0[of] + 1
    ol_i = nurand(rng, int(g["nurand_item_a"]), 1, I, i_run, n_lines)
    ol_qty = rng.integers(1, 11, n_lines)
    remote = (rng.random(n_lines) < float(g["remote_line_share"])) & (W > 1)
    ol_supply = np.where(remote, _other_warehouse(rng, no_w[of], W),
                         no_w[of])
    stock_row = (ol_supply - 1) * I + ol_i - 1
    ol_amount = ol_qty * loaded.rows["item"]["i_price"][ol_i - 1]
    # ol_dist_info is the stock row's s_dist_xx of the order's district
    ol_info = np.empty(n_lines, dtype="S24")
    for d in range(1, D + 1):
        mine = no_d[of] == d
        ol_info[mine] = loaded.rows["stock"][f"s_dist_{d:02d}"][
            stock_row[mine]]
    all_local = np.ones(len(no), dtype=np.int64)
    all_local[of[remote]] = 0

    # -- payment -------------------------------------------------------------
    pay = np.flatnonzero(kind == PAYMENT_TX)
    pay_w, pay_d = tx_w[pay], tx_d[pay]
    pay_amount = rng.integers(100, 500_001, len(pay))
    away = (rng.random(len(pay)) < float(g["remote_payment_share"])) & (W > 1)
    pay_cw = np.where(away, _other_warehouse(rng, pay_w, W), pay_w)
    pay_cd = np.where(away, rng.integers(1, D + 1, len(pay)), pay_d)
    pay_c = nurand(rng, int(g["nurand_customer_a"]), 1, C, c_run, len(pay))

    # -- delivery ------------------------------------------------------------
    dl = np.flatnonzero(kind == DELIVERY_TX)
    dl_w = tx_w[dl]
    dl_carrier = rng.integers(1, 11, len(dl))
    took = np.zeros((len(dl), D), dtype=bool)
    took_o = np.zeros((len(dl), D), dtype=np.int64)
    for w in range(1, W + 1):
        mine = np.flatnonzero(dl_w == w)
        for d in range(D):
            grp = (w - 1) * D + d
            entered = no[by_group[group_start[grp]:group_start[grp + 1]]]
            got = _delivered(len(mine), U + np.searchsorted(entered, dl[mine]))
            took[mine, d] = got
            took_o[mine, d] = O - U + np.cumsum(got)
    # every order there is, the loaded ones first, then the stream's
    orders = loaded.rows["orders"]
    lines = loaded.rows["order_line"]
    all_c = np.concatenate((orders["o_c_id"], no_c))
    all_cnt = np.concatenate((orders["o_ol_cnt"], no_cnt))
    all_entry = np.concatenate((orders["o_entry_d"], tx_us[no]))
    order_local = np.concatenate((orders["o_all_local"], all_local))
    all_line0 = np.concatenate((loaded.line_start[:-1],
                                loaded.line_start[-1] + no_line0[:-1]))
    line_i = np.concatenate((lines["ol_i_id"], ol_i))
    line_supply = np.concatenate((lines["ol_supply_w_id"], ol_supply))
    line_qty = np.concatenate((lines["ol_quantity"], ol_qty))
    line_amount = np.concatenate((lines["ol_amount"], ol_amount))
    line_info = np.concatenate((lines["ol_dist_info"], ol_info))
    amount_before = np.concatenate(([0], np.cumsum(line_amount)))
    dl_tx, dl_d0 = np.nonzero(took)          # delivered orders, tx by tx
    dl_o = took_o[dl_tx, dl_d0]
    dl_group = (dl_w[dl_tx] - 1) * D + dl_d0
    streamed = dl_o > O
    entered_at = np.clip(group_start[dl_group] + dl_o - O - 1, 0,
                         max(0, len(no) - 1))
    dl_order = np.where(
        streamed, len(orders["o_id"]) + (by_group[entered_at] if len(no)
                                         else 0), dl_group * O + dl_o - 1)
    dl_cnt = all_cnt[dl_order]
    dl_total = amount_before[all_line0[dl_order] + dl_cnt] \
        - amount_before[all_line0[dl_order]]
    dl_m = took.sum(axis=1)                  # orders a delivery took
    dl_lines = np.bincount(dl_tx, weights=dl_cnt,
                           minlength=len(dl)).astype(np.int64)
    dl_j = np.arange(len(dl_tx)) - np.repeat(
        np.concatenate(([0], np.cumsum(dl_m)[:-1])), dl_m)
    dl_line_of = np.repeat(np.arange(len(dl_tx)), dl_cnt)
    dl_line0 = np.concatenate(([0], np.cumsum(dl_cnt)))
    dl_line_no = np.arange(int(dl_line0[-1])) - dl_line0[dl_line_of]
    dl_line = all_line0[dl_order][dl_line_of] + dl_line_no
    # a delivery's lines in the order its orders were taken
    dl_first_line = np.concatenate(([0], np.cumsum(dl_lines)[:-1]))
    dl_line_at = np.arange(len(dl_line_of)) \
        - dl_first_line[dl_tx[dl_line_of]]

    # -- where every transaction sits ----------------------------------------
    every = int(g.get("bulk_every_transactions", 0))
    bulk_rows = int(g["bulk_rows"]) if every else 0
    n_bulk = N // every if every else 0
    events = np.zeros(N + n_bulk, dtype=np.int64)
    at = np.arange(N) + (np.arange(N) // every if every else 0)
    events[at[no]] = 3 + 2 * no_cnt
    events[at[pay]] = 3
    events[at[dl]] = 3 * dl_m + dl_lines
    bulk_at = (np.arange(n_bulk) + 1) * every + np.arange(n_bulk)
    events[bulk_at] = bulk_rows
    first = np.concatenate(([0], np.cumsum(events)))
    no_e, pay_e, dl_e = first[at[no]], first[at[pay]], first[at[dl]]
    total = int(first[-1])
    table = np.zeros(total, dtype=np.uint8)
    op = np.zeros(total, dtype=np.uint8)
    out = {}

    def place(t: int, part: dict) -> "dict | None":
        if not part or not len(part["pos"]):
            return None
        table[part["pos"]] = t
        op[part["pos"]] = part["op"]
        return part

    def ops(code: int, n: int) -> np.ndarray:
        return np.full(n, code, dtype=np.uint8)

    # warehouse: payments alone
    part = place(WAREHOUSE, {"pos": pay_e, "op": ops(UPDATE, len(pay)),
                             "row": pay_w - 1, "amount": pay_amount})
    if part:
        (paid,) = _running(part["row"], part["amount"])
        out[WAREHOUSE] = TableEvents(_cols(tables[WAREHOUSE], _rows_after(
            loaded.rows["warehouse"], part["row"], w_ytd=paid)))

    # district: a new-order takes the next order id, a payment adds to d_ytd
    part = place(DISTRICT, _merged([
        {"pos": no_e, "op": ops(UPDATE, len(no)), "row": no_group,
         "amount": np.zeros(len(no), dtype=np.int64),
         "took": np.ones(len(no), dtype=np.int64)},
        {"pos": pay_e + 1, "op": ops(UPDATE, len(pay)),
         "row": (pay_w - 1) * D + pay_d - 1, "amount": pay_amount,
         "took": np.zeros(len(pay), dtype=np.int64)}]))
    if part:
        paid, taken = _running(part["row"], part["amount"], part["took"])
        out[DISTRICT] = TableEvents(_cols(tables[DISTRICT], _rows_after(
            loaded.rows["district"], part["row"], d_ytd=paid,
            d_next_o_id=taken)))

    # customer: a payment, or the delivery of one of their orders
    dl_cust_row = dl_group * C + all_c[dl_order] - 1
    part = place(CUSTOMER, _merged([
        {"pos": pay_e + 2, "op": ops(UPDATE, len(pay)),
         "row": ((pay_cw - 1) * D + pay_cd - 1) * C + pay_c - 1,
         "balance": -pay_amount, "paid": pay_amount,
         "payments": np.ones(len(pay), dtype=np.int64),
         "deliveries": np.zeros(len(pay), dtype=np.int64),
         "d_id": pay_d, "w_id": pay_w},
        {"pos": dl_e[dl_tx] + 2 * dl_m[dl_tx] + dl_lines[dl_tx] + dl_j,
         "op": ops(UPDATE, len(dl_tx)), "row": dl_cust_row,
         "balance": dl_total, "paid": np.zeros(len(dl_tx), dtype=np.int64),
         "payments": np.zeros(len(dl_tx), dtype=np.int64),
         "deliveries": np.ones(len(dl_tx), dtype=np.int64),
         "d_id": np.zeros(len(dl_tx), dtype=np.int64),
         "w_id": np.zeros(len(dl_tx), dtype=np.int64)}]))
    if part:
        balance, paid, payments, deliveries = _running(
            part["row"], part["balance"], part["paid"], part["payments"],
            part["deliveries"])
        values = _rows_after(
            loaded.rows["customer"], part["row"], c_balance=balance,
            c_ytd_payment=paid, c_payment_cnt=payments,
            c_delivery_cnt=deliveries)
        _bad_credit_data(values, part)
        out[CUSTOMER] = TableEvents(_cols(tables[CUSTOMER], values))

    # new_order: entered by a new-order, deleted by the delivery
    part = place(NEW_ORDER, _merged([
        {"pos": no_e + 2, "op": ops(INSERT, len(no)), "no_o_id": no_o,
         "no_d_id": no_d, "no_w_id": no_w},
        {"pos": dl_e[dl_tx] + dl_j, "op": ops(DELETE, len(dl_tx)),
         "no_o_id": dl_o, "no_d_id": dl_d0 + 1, "no_w_id": dl_w[dl_tx]}]))
    if part:
        cols = _cols(tables[NEW_ORDER], part)
        out[NEW_ORDER] = TableEvents(cols, cols)

    # orders: entered with no carrier, which the delivery sets
    part = place(ORDERS, _merged([
        {"pos": no_e + 1, "op": ops(INSERT, len(no)), "o_id": no_o,
         "o_d_id": no_d, "o_w_id": no_w, "o_c_id": no_c,
         "o_entry_d": tx_us[no],
         "o_carrier_id": np.zeros(len(no), dtype=np.int64),
         "o_ol_cnt": no_cnt, "o_all_local": all_local,
         "no_carrier": np.ones(len(no), dtype=bool)},
        {"pos": dl_e[dl_tx] + dl_m[dl_tx] + dl_j,
         "op": ops(UPDATE, len(dl_tx)), "o_id": dl_o, "o_d_id": dl_d0 + 1,
         "o_w_id": dl_w[dl_tx], "o_c_id": all_c[dl_order],
         "o_entry_d": all_entry[dl_order],
         "o_carrier_id": dl_carrier[dl_tx], "o_ol_cnt": dl_cnt,
         "o_all_local": order_local[dl_order],
         "no_carrier": np.zeros(len(dl_tx), dtype=bool)}]))
    if part:
        out[ORDERS] = TableEvents(_cols(
            tables[ORDERS], part, {"o_carrier_id": part["no_carrier"]}))

    # order_line: an order's lines, their delivery stamp, and the bulk
    brng = _rng(seed, 11)
    n = n_bulk * bulk_rows
    q = np.arange(n) // MEAN_OL_CNT  # the order of a warehouse being loaded
    bulk_delivered = q % O < O - U
    part = place(ORDER_LINE, _merged([
        {"pos": no_e[of] + 3 + no_cnt[of] + number - 1,
         "op": ops(INSERT, n_lines), "ol_o_id": no_o[of], "ol_d_id": no_d[of],
         "ol_w_id": no_w[of], "ol_number": number, "ol_i_id": ol_i,
         "ol_supply_w_id": ol_supply,
         "ol_delivery_d": np.zeros(n_lines, dtype=np.int64),
         "ol_quantity": ol_qty, "ol_amount": ol_amount,
         "ol_dist_info": ol_info, "undelivered": np.ones(n_lines, bool)},
        {"pos": dl_e[dl_tx][dl_line_of] + 2 * dl_m[dl_tx][dl_line_of]
         + dl_line_at, "op": ops(UPDATE, len(dl_line)),
         "ol_o_id": dl_o[dl_line_of], "ol_d_id": dl_d0[dl_line_of] + 1,
         "ol_w_id": dl_w[dl_tx][dl_line_of], "ol_number": dl_line_no + 1,
         "ol_i_id": line_i[dl_line], "ol_supply_w_id": line_supply[dl_line],
         "ol_delivery_d": tx_us[dl][dl_tx][dl_line_of],
         "ol_quantity": line_qty[dl_line], "ol_amount": line_amount[dl_line],
         "ol_dist_info": line_info[dl_line],
         "undelivered": np.zeros(len(dl_line), bool)},
        {"pos": np.repeat(first[bulk_at], bulk_rows)
         + np.tile(np.arange(bulk_rows), n_bulk),
         "op": ops(INSERT, n), "ol_o_id": q % O + 1,
         "ol_d_id": q // O % D + 1, "ol_w_id": W + 1 + q // (O * D),
         "ol_number": np.arange(n) % MEAN_OL_CNT + 1,
         "ol_i_id": brng.integers(1, I + 1, n),
         "ol_supply_w_id": W + 1 + q // (O * D),
         "ol_delivery_d": np.where(bulk_delivered, LOADED_US, 0),
         "ol_quantity": np.full(n, 5, dtype=np.int64),
         "ol_amount": np.where(bulk_delivered, 0,
                               brng.integers(1, 1_000_000, n)),
         "ol_dist_info": _letters(brng, n, 24, 24),
         "undelivered": ~bulk_delivered}]))
    if part:
        out[ORDER_LINE] = TableEvents(_cols(
            tables[ORDER_LINE], part, {"ol_delivery_d": part["undelivered"]}))

    # stock: each line of a new-order takes its quantity (§2.4.2.2)
    part = place(STOCK, {"pos": no_e[of] + 3 + number - 1,
                         "op": ops(UPDATE, n_lines), "row": stock_row,
                         "qty": ol_qty, "remote": remote.astype(np.int64)})
    if part:
        taken, orders_n, remotes = _running(
            part["row"], part["qty"], np.ones(n_lines, dtype=np.int64),
            part["remote"])
        values = _rows_after(
            loaded.rows["stock"], part["row"], s_quantity=-taken,
            s_ytd=taken, s_order_cnt=orders_n, s_remote_cnt=remotes)
        # q - x where that leaves 10 or more, else q - x + 91
        values["s_quantity"] = 10 + (values["s_quantity"] - 10) % 91
        out[STOCK] = TableEvents(_cols(tables[STOCK], values))

    return Stream(TxLayout.build(events[events > 0]), table, op, out)


def _bad_credit_data(values: dict, part: dict) -> None:
    """§2.5.2.2: a payment by a customer with bad credit puts the
    payment's ids and amount before `c_data` and keeps 500 characters.
    The customers with bad credit are a tenth and their events few, so
    this one walks them in order."""
    bad = np.flatnonzero(values["c_credit"] == b"BC")
    if not len(bad):
        return
    data = values["c_data"]
    is_payment = part["payments"][bad] == 1
    now: dict = {}
    for at, row, paying in zip(bad.tolist(), part["row"][bad].tolist(),
                               is_payment.tolist()):
        held = now.get(row)
        if paying:
            held = (b"%d %d %d %d %d %.2f|" % (
                values["c_id"][at], values["c_d_id"][at],
                values["c_w_id"][at], part["d_id"][at], part["w_id"][at],
                part["paid"][at] / 100)
                + (held if held is not None else bytes(data[at])))[:500]
            now[row] = held
        if held is not None:
            data[at] = held
