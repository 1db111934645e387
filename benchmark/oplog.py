"""What a deployment's generator hands the harness: tables as typed numpy
columns and the change stream as an operation log.

Shared by the source (renders the log's bytes), the harness (warms the
programs the log's events will use) and the plain reference (holds the sink
to the log). Nothing here imports the program or JAX.

A configuration's file lists its published `tables` (or one `table`, as the
two pgbench configurations do): name, id, `replica_identity` (`d` default,
`f` full) and columns with `type`, `key` (true, or the 1-based position in a
key of several parts), `nullable`, and `modifier` / `text_bytes` where the
type has a width. Its `generator` names the file of `deployments/` (absent:
`pgbench_accounts`) that makes the data:

    snapshot(config, traffic, seed) -> {table id: [Col, ...]}
        the rows each table holds when the pipeline starts: what the
        initial copy sends and the reference's starting state
    stream(config, traffic, seed, seconds) -> Stream or None
        the change stream a CDC mix plays (None for a copy mix)

Column values by type: `bool` bool; `int2`/`int4`/`int8` integers; `float8`
float64; `numeric` int64 scaled by the column's `scale`; `date` int32 days
since 1970-01-01; `timestamp`/`timestamptz` int64 microseconds since
1970-01-01 (UTC); `bpchar`/`varchar`/`text`/`json`/`jsonb` numpy `S`
arrays of the server's text (a `bpchar` value without its padding), one
`bytes` value that every row shares, or `Coded`: int32 codes into a pool of
distinct values held once, for text too wide to pad every row to the widest.

`COMPARISONS` is the one place that says how the plain reference compares a
column, by its type; a configuration with a type it lacks is refused when
its tables are read.
"""

from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WAL_STEP = 8
BASE_LSN = 0x0100_0000  # where the source's WAL starts (slots are made here)
INSERT, UPDATE, DELETE = ord("I"), ord("U"), ord("D")
DEFAULT_GENERATOR = "pgbench_accounts"
# how the reference compares a column's values, by the column's type:
#   typed    the generator's numbers exactly (a float8 bit for bit)
#   numeric  as decimals
#   text     byte for byte (bpchar with the server's padding)
#   json     as JSON values: objects as unordered maps, arrays in order,
#            numbers as exact decimals, JSON null apart from SQL NULL
COMPARISONS = {
    "bool": "typed", "int2": "typed", "int4": "typed", "int8": "typed",
    "float8": "typed", "date": "typed", "timestamp": "typed",
    "timestamptz": "typed", "numeric": "numeric", "bpchar": "text",
    "varchar": "text", "text": "text", "json": "json", "jsonb": "json"}


# ---------------------------------------------------------------------------
# the configuration's tables
# ---------------------------------------------------------------------------


def tables_of(config: dict) -> list:
    """The published tables of a configuration, in the order the log's
    `table` indices count them. A column whose type has no comparison is
    refused here, by name, before anything renders or runs."""
    tables = list(config["tables"]) if "tables" in config \
        else [config["table"]]
    for table in tables:
        for column in table.get("columns", ()):
            if column["type"] not in COMPARISONS:
                raise ValueError(
                    f"{table['name']}.{column['name']}: type "
                    f"{column['type']!r} has no comparison in the benchmark "
                    f"(oplog.COMPARISONS: {', '.join(sorted(COMPARISONS))})")
    return tables


def comparison(column: dict) -> str:
    """How the reference compares this column: `typed`, `numeric`, `text`
    or `json` (COMPARISONS)."""
    return COMPARISONS[column["type"]]


def as_text(column: dict) -> bool:
    """Whether the generator holds this column's values as their text:
    `S` arrays, one shared `bytes` value, or `Coded`."""
    return COMPARISONS[column["type"]] in ("text", "json")


def key_indices(table: dict) -> list:
    """Column indices of the table's key, in key order."""
    keyed = [(1 if c["key"] is True else int(c["key"]), i)
             for i, c in enumerate(table["columns"]) if c.get("key")]
    return [i for _, i in sorted(keyed)]


def char_width(column: dict) -> int:
    """The n of a bpchar(n) or varchar(n) column."""
    return int(column["text_bytes"]) if "text_bytes" in column \
        else int(column["modifier"]) - 4


def load_generator(config: dict, config_path: str):
    """The module that makes this configuration's data: `deployments/
    <generator>.py`, or that path taken from the configuration file's own
    directory (a deployment rehearsed with --config-file before it is a
    cell brings its generator with it)."""
    name = config.get("generator", DEFAULT_GENERATOR)
    tried = [os.path.join(HERE, "deployments", name + ".py"),
             os.path.normpath(os.path.join(
                 os.path.dirname(os.path.abspath(config_path)),
                 name + ".py"))]
    path = next((p for p in tried if os.path.exists(p)), None)
    if path is None:
        raise SystemExit(f"no generator {name!r}: looked for {tried}")
    spec = importlib.util.spec_from_file_location(
        "deployment_" + os.path.basename(name).replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# columns, the operation log and where it sits in the WAL
# ---------------------------------------------------------------------------


class Pool:
    """Distinct text values, held once, with what is made of them once per
    pool (a padded or escaped copy, their JSON spellings) kept beside."""

    def __init__(self, values):
        self.values = tuple(bytes(v) for v in values)
        self.lengths = np.fromiter(map(len, self.values), dtype=np.int64,
                                   count=len(self.values))
        self._made: dict = {}

    def __len__(self) -> int:
        return len(self.values)

    def made(self, key, fn):
        """`fn(self)`, made once for this pool under `key`."""
        if key not in self._made:
            self._made[key] = fn(self)
        return self._made[key]


class Coded:
    """Text values as int32 codes into a `Pool`: row i holds
    `pool.values[codes[i]]`. A row costs 4 bytes, whatever the widths, so a
    log of wide values (out-of-line TOAST text) is not padded to the widest
    row. Picking rows picks codes; a renderer or the reference makes the
    text of the rows at hand only. `np.asarray` gives the padded `S` array
    (a key's few bytes; never a wide column's)."""

    __slots__ = ("codes", "pool")

    def __init__(self, codes, pool):
        self.codes = np.asarray(codes, dtype=np.int32)
        self.pool = pool if isinstance(pool, Pool) else Pool(pool)

    def __len__(self) -> int:
        return len(self.codes)

    def __getitem__(self, rows) -> "Coded":
        return Coded(self.codes[rows], self.pool)

    def __array__(self, dtype=None, copy=None):
        padded = self.pool.made("S", lambda p: np.array(
            p.values or [b""], dtype="S"))
        out = padded[self.codes]
        return out if dtype is None else out.astype(dtype)

    def __eq__(self, other):
        return np.asarray(self) == np.asarray(other)

    def __ne__(self, other):
        return np.asarray(self) != np.asarray(other)

    __hash__ = None


@dataclass
class Col:
    """One column of some rows. `null[i]`: row i holds NULL there;
    `unchanged[i]` (new images of updates only): the value was not sent
    again (pgoutput's `u`, an unchanged TOAST value)."""

    values: "np.ndarray | bytes"
    null: "np.ndarray | None" = None
    unchanged: "np.ndarray | None" = None

    def pick(self, rows) -> "Col":
        """The rows `rows` (a slice or an index array) of this column."""
        return Col(self.values if isinstance(self.values, bytes)
                   else self.values[rows],
                   None if self.null is None else self.null[rows],
                   None if self.unchanged is None else self.unchanged[rows])


def n_rows(cols: list) -> int:
    """Row count of a list of Cols (a table whose every column is one
    shared `bytes` value has to carry a mask to have a length)."""
    for c in cols:
        for a in (c.values, c.null, c.unchanged):
            if isinstance(a, (np.ndarray, Coded)):
                return len(a)
    return 0


@dataclass
class TableEvents:
    """One table's events, in stream order. `new[c]` is the row after an
    insert or update (unused where the event is a delete), `old[c]` the
    whole row before an update or delete (unused where it is an insert;
    None for a table that only sees inserts). What of the old row goes on
    the wire — nothing, the key, all of it — follows from the table's
    replica identity and from whether the key changed."""

    new: list
    old: "list | None" = None


@dataclass
class TxLayout:
    """Where each transaction of a stream sits in the WAL (the fake
    database's own layout, `etl_tpu/postgres/fake.py`): every WAL entry
    advances the LSN by 8; a transaction of n events is BEGIN at B, events
    at B+8..B+8n, COMMIT at C=B+8(n+1), and ends at E=C+8, where the next
    BEGIN lands. It is durable once the flush position reaches E."""

    rows: np.ndarray        # int64[n_tx] events of each transaction
    begin_lsn: np.ndarray   # int64[n_tx]
    commit_lsn: np.ndarray  # int64[n_tx]
    end_lsn: np.ndarray     # int64[n_tx]

    @classmethod
    def build(cls, tx_rows, base_lsn: int = BASE_LSN) -> "TxLayout":
        rows = np.asarray(tx_rows, dtype=np.int64)
        span = WAL_STEP * (rows + 2)
        end = base_lsn + WAL_STEP + np.cumsum(span)
        return cls(rows, end - span, end - WAL_STEP, end)

    @property
    def starts(self) -> np.ndarray:
        """int64[n_tx+1]: index of each transaction's first event."""
        return np.concatenate(([0], np.cumsum(self.rows)))

    def durable_count(self, flush_lsn: int) -> int:
        """How many leading transactions the flush position has passed."""
        return int(np.searchsorted(self.end_lsn, flush_lsn, side="right"))

    def row_coordinates(self, k0: int, k1: int):
        """(commit_lsn, tx_ordinal) of every event of transactions
        k0..k1-1, in WAL order — what the program must attribute each to."""
        rows = self.rows[k0:k1]
        commit = np.repeat(self.commit_lsn[k0:k1], rows)
        starts = np.concatenate(([0], np.cumsum(rows)[:-1]))
        ordinal = np.arange(int(rows.sum()), dtype=np.int64) \
            - np.repeat(starts, rows)
        return commit, ordinal


@dataclass
class Stream:
    """The change stream as an operation log: per transaction its events in
    order (`layout.rows[k]` of them; lengths may differ), each event one
    table's insert, update or delete; events of several tables may
    interleave inside a transaction. `table[e]` indexes `tables_of(config)`;
    `events[t]` holds the images of table t's events in stream order."""

    layout: TxLayout
    table: np.ndarray   # uint8[n_events]
    op: np.ndarray      # uint8[n_events]: INSERT, UPDATE or DELETE
    events: dict        # {table index: TableEvents}

    def local_index(self) -> "np.ndarray | None":
        """Per event, its place among its own table's events; None where
        one table has them all (the place is the event's own index)."""
        if len(self.events) == 1:
            return None
        out = np.empty(len(self.table), dtype=np.int64)
        for t in self.events:
            mine = np.flatnonzero(self.table == t)
            out[mine] = np.arange(len(mine))
        return out
