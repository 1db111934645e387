"""Device-resident wire egress: render destination-ready text ON DEVICE.

The decode pipeline's last host stage — turning typed columns into wire
bytes (ClickHouse TSV fields, Snowpipe NDJSON values) — costs more than
the decode itself on the streaming path: per-batch numpy `astype("U21")`
round trips, per-value `str()` loops, and a Python `"\\t".join` per row.
This module moves the fixed-width, integer-arithmetic part of that work
into a SECOND jitted program that consumes the decode program's packed
`uint32[n_words, R]` words while they are still device-resident and
emits, per rendered column, left-aligned ASCII bytes plus per-row
lengths:

    egress(words) -> (ebytes uint8[R, sum(widths)], elens int32[R, n])

Renderable kinds are the ones whose canonical Postgres text is pure
integer arithmetic — bools, the int family (minimal decimal, the same
digits `str(int)` produces), dates and timestamps (civil-from-days,
`YYYY-MM-DD[ HH:MM:SS.ffffff]`, always 6 fractional digits like
`np.datetime_as_string(unit="us")`). Floats stay host-side (shortest
`repr` is not vectorizable) and strings ride Arrow buffers the staging
layer already gathers zero-copy.

Correctness stance: the program renders only TRUSTED rows — rows the
decode path itself verified (`ok` bits, no oversize, no nibble flag).
Everything else (NULLs, TOAST, specials like `infinity` — which can
never even appear in the packed words, the 23-bit zigzag day field
excludes the sentinels — and fallback rows) is rendered host-side by the
existing per-value oracle and spliced in whole, so the assembled wire
bytes are byte-identical to the host columnar encoders by construction.
The host twins in this module (`int_text_fixed` & co.) produce the same
buffers from a decoded `ColumnarBatch` when no device buffer landed, so
destinations have ONE fast assembly path with two byte-identical buffer
sources.

All device arithmetic is int32/uint32 (the ir-widening contract bans
64-bit creep); the program is elementwise along rows, so the mesh path
shards it over 'sp' with zero collectives and no donation (the decode
program's words stay alive for the normal unpack fetch).
"""

from __future__ import annotations

import dataclasses
import logging
import threading

import numpy as np

from .. import native
from ..models.pgtypes import CellKind
from ..telemetry.metrics import (ETL_EGRESS_ASSEMBLED_ROWS_TOTAL,
                                 ETL_EGRESS_NATIVE_ASSEMBLED_ROWS_TOTAL,
                                 registry)

log = logging.getLogger("etl_tpu.ops")

#: encoder names destinations declare via `Destination.egress_encoder`
ENCODER_TSV = "tsv"    # ClickHouse TSV fields (clickhouse.render_value)
ENCODER_JSON = "json"  # Snowpipe NDJSON values (snowflake JSON texts)

#: left-aligned output byte width per renderable kind (worst-case text)
_FIELD_WIDTH = {
    CellKind.BOOL: 5,          # "false"
    CellKind.I16: 6,           # "-32768"
    CellKind.I32: 11,          # "-2147483648"
    CellKind.U32: 10,          # "4294967295"
    CellKind.I64: 20,          # "-9223372036854775808"
    CellKind.DATE: 10,         # "YYYY-MM-DD"
    CellKind.TIMESTAMP: 26,    # "YYYY-MM-DD HH:MM:SS.ffffff"
    CellKind.TIMESTAMPTZ: 26,
}

#: max decimal digits of the magnitude per int-family kind
_MAX_DIGITS = {CellKind.I16: 5, CellKind.I32: 10, CellKind.U32: 10}

_INT_KINDS = frozenset({CellKind.I16, CellKind.I32, CellKind.U32})

#: kinds each encoder can render on device. TSV covers the temporals
#: (ClickHouse wants "YYYY-MM-DD HH:MM:SS.ffffff" — exactly the civil
#: rendering); NDJSON keeps temporals host-side (snowflake's JSON text
#: goes through the generic `json.dumps(encode_value(...))` path whose
#: quoting/format is not worth re-specifying on device).
ENCODER_KINDS = {
    ENCODER_TSV: frozenset({
        CellKind.BOOL, CellKind.I16, CellKind.I32, CellKind.U32,
        CellKind.I64, CellKind.DATE, CellKind.TIMESTAMP,
        CellKind.TIMESTAMPTZ,
    }),
    ENCODER_JSON: frozenset({
        CellKind.BOOL, CellKind.I16, CellKind.I32, CellKind.U32,
        CellKind.I64,
    }),
}

#: widest schema slice the egress program renders: past this the unrolled
#: per-digit selects bloat the program for columns the host renders
#: about as fast anyway (the win concentrates in the common narrow CDC
#: schemas)
EGRESS_MAX_COLS = 32


@dataclasses.dataclass(frozen=True)
class EgressPlan:
    """Static render plan for one (canonical specs, encoder) signature.
    `slots` are canonical slot indices into the pspecs the decode
    program packed — completion maps real schema columns onto them
    through the canonical plan's `slot_of`, exactly like column unpack."""

    encoder: str
    slots: tuple[int, ...]
    kinds: tuple[CellKind, ...]
    widths: tuple[int, ...]

    @property
    def total_width(self) -> int:
        return sum(self.widths)

    @property
    def offsets(self) -> tuple[int, ...]:
        out, off = [], 0
        for w in self.widths:
            out.append(off)
            off += w
        return tuple(out)


def plan_for_specs(pspecs: tuple, encoder: str) -> "EgressPlan | None":
    """The render plan for a packed layout, or None when the encoder is
    unknown, nothing in the layout is device-renderable, or the schema
    is too wide to be worth unrolling."""
    kinds_ok = ENCODER_KINDS.get(encoder)
    if kinds_ok is None or not pspecs:
        return None
    slots, kinds, widths = [], [], []
    for j, (_, kind, _, _) in enumerate(pspecs):
        if kind in kinds_ok:
            slots.append(j)
            kinds.append(kind)
            widths.append(_FIELD_WIDTH[kind])
    if not slots or len(slots) > EGRESS_MAX_COLS:
        return None
    return EgressPlan(encoder, tuple(slots), tuple(kinds), tuple(widths))


# ---------------------------------------------------------------------------
# the device program
# ---------------------------------------------------------------------------

def _slot_fields(layout, slot: int) -> dict:
    return {s.comp: s for s in layout.slots[slot]}


def _extract(words, slot) -> "object":
    """Raw uint32[R] field bytes of one packed slot (pre-zigzag) —
    the jnp mirror of bitpack.unpack_host's shift/mask math."""
    import jax.numpy as jnp

    w, sh = divmod(slot.bit_off, 32)
    v = words[w] >> sh
    if sh + slot.bits > 32:
        v = v | (words[w + 1] << (32 - sh))
    if slot.bits < 32:
        v = v & jnp.uint32((1 << slot.bits) - 1)
    return v


def _signed(raw):
    """Zigzag-decode a raw field to int32."""
    import jax.numpy as jnp

    u1 = (raw & jnp.uint32(1)).astype(jnp.int32)
    return (raw >> 1).astype(jnp.int32) ^ (-u1)


def _plain(raw):
    import jax.numpy as jnp

    return raw.astype(jnp.int32)


def _field_value(words, fields: dict, name: str, n_rows: int):
    """Decoded int32[R] component (zeros when the layout omitted it)."""
    import jax.numpy as jnp

    s = fields.get(name)
    if s is None:
        return jnp.zeros((n_rows,), dtype=jnp.int32)
    raw = _extract(words, s)
    return _signed(raw) if s.zigzag else _plain(raw)


def _digits_to_bytes(digit_at, nd, neg, width: int):
    """Left-aligned minimal-decimal bytes from a digit extractor.
    `digit_at(k)` returns the int32 digit at power-of-ten index `k`
    (k may be out of range for short numbers — extractors clip)."""
    import jax.numpy as jnp

    L = nd + neg
    out = []
    for p in range(width):
        k = nd - 1 - p + neg
        core = 48 + digit_at(k)
        if p == 0:
            core = jnp.where(neg > 0, jnp.int32(45), core)  # '-'
        out.append(jnp.where(p < L, core, 0).astype(jnp.uint8))
    return out, L


def _render_u32_family(mag, neg, width: int, max_digits: int):
    """mag uint32[R], neg int32[R] in {0,1} → minimal decimal."""
    import jax.numpy as jnp

    nd = jnp.ones(mag.shape, dtype=jnp.int32)
    for k in range(1, max_digits):
        nd = nd + (mag >= jnp.uint32(10 ** k)).astype(jnp.int32)
    p10 = jnp.array([10 ** i for i in range(max_digits)], dtype=jnp.uint32)

    def digit_at(k):
        kc = jnp.clip(k, 0, max_digits - 1)
        return ((mag // p10[kc]) % 10).astype(jnp.int32)

    return _digits_to_bytes(digit_at, nd, neg, width)


def _limb_digits(limb, hi: int):
    """Digit count of a base-10^9 limb (1..9), uint32 input."""
    import jax.numpy as jnp

    nd = jnp.ones(limb.shape, dtype=jnp.int32)
    for k in range(1, hi):
        nd = nd + (limb >= jnp.uint32(10 ** k)).astype(jnp.int32)
    return nd


def _render_i64(neg, l0, l1, l2, width: int):
    """Minimal decimal of a base-10^9 limbed int64 magnitude. The pack
    layout bounds l2 <= 9 (a 19-digit magnitude's top limb), so digit 18
    is l2 itself."""
    import jax.numpy as jnp

    nd = jnp.where(
        l2 > 0, jnp.int32(19),
        jnp.where(l1 > 0, 9 + _limb_digits(l1, 9), _limb_digits(l0, 9)))
    p10 = jnp.array([10 ** i for i in range(9)], dtype=jnp.uint32)

    def digit_at(k):
        kc = jnp.clip(k, 0, 18)
        d0 = (l0 // p10[jnp.clip(kc, 0, 8)]) % 10
        d1 = (l1 // p10[jnp.clip(kc - 9, 0, 8)]) % 10
        return jnp.where(kc < 9, d0.astype(jnp.int32),
                         jnp.where(kc < 18, d1.astype(jnp.int32),
                                   (l2 % 10).astype(jnp.int32)))

    return _digits_to_bytes(digit_at, nd, neg.astype(jnp.int32), width)


_TRUE = (116, 114, 117, 101, 0)    # "true\0"
_FALSE = (102, 97, 108, 115, 101)  # "false"


def _render_bool(v, width: int):
    import jax.numpy as jnp

    t = v > 0
    out = [jnp.where(t, jnp.uint8(_TRUE[p]), jnp.uint8(_FALSE[p]))
           for p in range(width)]
    return out, jnp.where(t, jnp.int32(4), jnp.int32(5))


def _civil(days):
    """Howard Hinnant's civil_from_days, all int32. Trusted rows carry
    days for years 1..9999 (the parser's ok range), so z stays positive
    and every floor division is over non-negative operands."""
    z = days + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    import jax.numpy as jnp

    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2).astype(jnp.int32)
    return y, m, d


def _date_bytes(y, m, d) -> list:
    import jax.numpy as jnp

    def u8(x):
        return x.astype(jnp.uint8)

    def c(ch):
        return jnp.full(y.shape, ch, dtype=jnp.uint8)

    return [u8(48 + (y // 1000) % 10), u8(48 + (y // 100) % 10),
            u8(48 + (y // 10) % 10), u8(48 + y % 10), c(45),
            u8(48 + m // 10), u8(48 + m % 10), c(45),
            u8(48 + d // 10), u8(48 + d % 10)]


def _render_date(days, width: int):
    import jax.numpy as jnp

    y, m, d = _civil(days)
    return _date_bytes(y, m, d), jnp.full(days.shape, width,
                                          dtype=jnp.int32)


def _render_timestamp(days, ms, us, width: int):
    """`YYYY-MM-DD HH:MM:SS.ffffff` — np.datetime_as_string(unit='us')
    with 'T' already a space. TIMESTAMPTZ rows arrive with ms shifted by
    the zone offset (possibly negative / >= a day): normalize into
    [0, 86_400_000) and carry whole days first."""
    import jax.numpy as jnp

    day_adj = ms // 86_400_000  # floor division: -1/0/+1
    ms = ms - day_adj * 86_400_000
    days = days + day_adj
    y, m, d = _civil(days)
    hh = ms // 3_600_000
    mi = (ms // 60_000) % 60
    ss = (ms // 1_000) % 60
    frac = (ms % 1_000) * 1_000 + us

    def u8(x):
        return x.astype(jnp.uint8)

    def c(ch):
        return jnp.full(days.shape, ch, dtype=jnp.uint8)

    out = _date_bytes(y, m, d)
    out.append(c(32))  # ' '
    out += [u8(48 + hh // 10), u8(48 + hh % 10), c(58),
            u8(48 + mi // 10), u8(48 + mi % 10), c(58),
            u8(48 + ss // 10), u8(48 + ss % 10), c(46)]
    for p in (100_000, 10_000, 1_000, 100, 10, 1):
        out.append(u8(48 + (frac // p) % 10))
    return out, jnp.full(days.shape, width, dtype=jnp.int32)


def build_egress_program(pspecs: tuple, plan: EgressPlan):
    """The (unjitted) render body: words uint32[n_words, R] →
    (ebytes uint8[R, total_width], elens int32[R, n_rendered])."""
    from . import bitpack

    layout = bitpack.layout_for_specs(pspecs)

    def fn(words):
        import jax.numpy as jnp

        n_rows = words.shape[1]
        bufs, lens = [], []
        for slot, kind, width in zip(plan.slots, plan.kinds, plan.widths):
            fields = _slot_fields(layout, slot)

            def get(name, fields=fields):
                return _field_value(words, fields, name, n_rows)

            if kind is CellKind.BOOL:
                bs, L = _render_bool(get("v"), width)
            elif kind in _INT_KINDS:
                s = fields["v"]
                raw = _extract(words, s)
                if s.zigzag:
                    mag = (raw >> 1) + (raw & jnp.uint32(1))
                    neg = (raw & jnp.uint32(1)).astype(jnp.int32)
                else:
                    mag, neg = raw, jnp.zeros((n_rows,), dtype=jnp.int32)
                bs, L = _render_u32_family(mag, neg, width,
                                           _MAX_DIGITS[kind])
            elif kind is CellKind.I64:
                raws = {}
                for name in ("neg", "l0", "l1", "l2"):
                    s = fields.get(name)
                    raws[name] = _extract(words, s) if s is not None \
                        else jnp.zeros((n_rows,), dtype=jnp.uint32)
                bs, L = _render_i64(raws["neg"].astype(jnp.int32),
                                    raws["l0"], raws["l1"], raws["l2"],
                                    width)
            elif kind is CellKind.DATE:
                bs, L = _render_date(get("days"), width)
            elif kind in (CellKind.TIMESTAMP, CellKind.TIMESTAMPTZ):
                bs, L = _render_timestamp(get("days"), get("ms"),
                                          get("us"), width)
            else:  # pragma: no cover — plan_for_specs filters kinds
                raise AssertionError(kind)
            bufs.append(jnp.stack(bs, axis=1))
            lens.append(L)
        return (jnp.concatenate(bufs, axis=1),
                jnp.stack(lens, axis=1).astype(jnp.int32))

    def etl_egress(words):
        import jax

        with jax.named_scope("render"):
            return fn(words)

    return etl_egress


def build_egress_fn(pspecs: tuple, plan: EgressPlan, mesh=None):
    """Jit the render body. On the mesh path the words arrive sharded
    over rows on axis 1 (the decode program's output spec) and both
    outputs leave row-sharded on axis 0 — elementwise along rows, so the
    partitioner keeps every shard local (the ir-collective contract
    holds for egress programs too). No donation: the words buffer is
    still the decode fetch's source."""
    import jax

    body = build_egress_program(pspecs, plan)
    if mesh is None:
        return jax.jit(body)
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return jax.jit(
        body,
        in_shardings=(NamedSharding(mesh, P(None, "sp")),),
        out_shardings=(NamedSharding(mesh, P("sp", None)),
                       NamedSharding(mesh, P("sp", None))))


def lower_egress_program(pspecs: tuple, encoder: str, row_capacity: int,
                         mesh=None):
    """(jitted, example_avals, lowered) for one egress program — the IR
    tier's lowering entry (analysis/ir/runner.py), built through the
    SAME constructor production dispatch uses so the verified artifact
    is the shipped one. Raises ValueError when the layout has no
    renderable fields under `encoder`."""
    import jax
    import jax.numpy as jnp

    from .bitpack import layout_for_specs

    plan = plan_for_specs(pspecs, encoder)
    if plan is None:
        raise ValueError(f"no egress plan for encoder {encoder!r} over "
                         f"{len(pspecs)} specs")
    fn = build_egress_fn(pspecs, plan, mesh=mesh)
    n_words = layout_for_specs(pspecs).n_words
    avals = (jax.ShapeDtypeStruct((n_words, row_capacity), jnp.uint32),)
    return fn, avals, fn.lower(*avals)


def egress_fn_key(row_capacity: int, pspecs: tuple, encoder: str,
                  mesh_fp, host: bool) -> tuple:
    """Module program-cache key for one egress program. Same tuple
    arity/ordering as decode keys so the program store, the observed-
    signature recorder and the warm-restart path handle it unchanged;
    the ("egress", encoder) marker rides the pred_fp slot (decode keys
    hold None or a predicate fingerprint there — never a 2-tuple
    starting with "egress", so the spaces cannot collide). key[-1] is
    the placement, as in decode keys: True when the words this program
    renders live on the host CPU backend — the store reloads a
    serialized executable onto the devices its key names."""
    return (row_capacity, pspecs, False, mesh_fp, False,
            ("egress", encoder), host)


def is_egress_key(key: tuple) -> bool:
    marker = key[5]
    return isinstance(marker, tuple) and marker[:1] == ("egress",)


# background-compile bookkeeping, mirroring engine._BG_COMPILE_KEYS: a
# cold egress program must never block a streaming dispatch — batches
# simply ship without device egress (destinations fall back to the host
# twins) until the compile lands
_EGRESS_BG_KEYS: set = set()
_EGRESS_BG_FAILED: set = set()
_EGRESS_BG_LOCK = threading.Lock()


def count_failure() -> None:
    """One egress build, dispatch or materialization raised and its batch
    shipped without wire buffers: availability code, but countable."""
    from ..telemetry.metrics import (ETL_EGRESS_DEVICE_FAILURES_TOTAL,
                                     registry)

    registry.counter_inc(ETL_EGRESS_DEVICE_FAILURES_TOTAL)


def _build_failed(key: tuple) -> None:
    with _EGRESS_BG_LOCK:
        _EGRESS_BG_FAILED.add(key)
    count_failure()


def egress_fn_ready(key: tuple, builder, example_args: tuple,
                    blocking: bool = False):
    """The egress program for `key`, or None while it compiles in the
    background. Memory → disk → (inline when `blocking`, else
    background thread) — the same ladder as the decode host path."""
    from . import program_store
    from .engine import _shared_fn_get, _shared_fn_put

    fn = _shared_fn_get(key)
    if fn is not None:
        return fn
    with _EGRESS_BG_LOCK:
        if key in _EGRESS_BG_FAILED:
            return None
        building = key in _EGRESS_BG_KEYS
    if building:
        return None
    fn = program_store.try_load(key, record_absent=False)
    if fn is not None:
        _shared_fn_put(key, fn)
        return fn
    if blocking:
        try:
            fn = program_store.acquire(key, builder, example_args)
        except Exception:
            _build_failed(key)
            log.warning("egress program build failed; wire encoding "
                        "stays on the host twins", exc_info=True)
            return None
        _shared_fn_put(key, fn)
        return fn
    with _EGRESS_BG_LOCK:
        if key in _EGRESS_BG_KEYS or key in _EGRESS_BG_FAILED:
            return None
        _EGRESS_BG_KEYS.add(key)

    def work() -> None:
        try:
            import jax

            f = program_store.acquire(key, builder, example_args)
            jax.block_until_ready(f(*example_args))
            _shared_fn_put(key, f)
        except Exception:
            _build_failed(key)
            log.warning("background egress-program compile failed; wire "
                        "encoding stays on the host twins", exc_info=True)
        finally:
            with _EGRESS_BG_LOCK:
                _EGRESS_BG_KEYS.discard(key)

    try:
        # non-daemon for the same reason as the decode background
        # compile: a daemon thread killed mid-XLA-build aborts the
        # process from C++ at interpreter teardown
        threading.Thread(target=work, name="etl-egress-bg-compile",
                         daemon=False).start()
    except RuntimeError:
        with _EGRESS_BG_LOCK:
            _EGRESS_BG_KEYS.discard(key)
            _EGRESS_BG_FAILED.add(key)
    return None


def reset_for_tests() -> None:
    with _EGRESS_BG_LOCK:
        _EGRESS_BG_KEYS.clear()
        _EGRESS_BG_FAILED.clear()


# ---------------------------------------------------------------------------
# fetched-egress transport
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceEgress:
    """Wire-ready text buffers riding a decoded batch
    (`ColumnarBatch.device_egress`). `fields` maps SCHEMA column index →
    (bytes uint8[n, W] left-aligned, lens int32[n]); `untrusted` lists
    row indices whose device bytes must not be used (fallback rows,
    oracle fixups) — destinations render those rows per-value and splice
    whole lines."""

    encoder: str
    n_rows: int
    fields: dict
    untrusted: np.ndarray

    def field(self, col_index: int):
        return self.fields.get(col_index)

    @classmethod
    def concat(cls, parts: list) -> "DeviceEgress | None":
        """Merge per-event-batch egress into one buffer set for a
        coalesced run. All-or-nothing: one part without device buffers
        (or a field-set/encoder mismatch) drops the merged fast path —
        correctness never depends on egress being present."""
        if not parts or any(p is None for p in parts):
            return None
        enc = parts[0].encoder
        keys = set(parts[0].fields)
        if any(p.encoder != enc or set(p.fields) != keys for p in parts):
            return None
        fields: dict = {}
        for k in keys:
            fields[k] = (
                np.concatenate([p.fields[k][0] for p in parts], axis=0),
                np.concatenate([p.fields[k][1] for p in parts]))
        untr, off = [], 0
        for p in parts:
            if p.untrusted.size:
                untr.append(p.untrusted + off)
            off += p.n_rows
        return cls(enc, off, fields,
                   np.concatenate(untr) if untr
                   else np.zeros(0, dtype=np.int64))


def materialize(egress_out: tuple, plan, dense, n: int,
                untrusted) -> "DeviceEgress | None":
    """Fetch an egress dispatch's outputs and index them by schema
    column. `plan` is the batch's canonical pack plan (None = identity):
    real column j rendered from canonical slot plan.slot_of[j], the
    mirror of `_assemble`'s unpack mapping."""
    ebytes_d, elens_d, eplan = egress_out
    ebytes = np.asarray(ebytes_d)
    elens = np.asarray(elens_d)
    pos_of = {s: i for i, s in enumerate(eplan.slots)}
    offs = eplan.offsets
    fields: dict = {}
    for j, spec in enumerate(dense):
        slot = plan.slot_of[j] if plan is not None else j
        i = pos_of.get(slot)
        if i is None or eplan.kinds[i] is not spec.kind:
            continue
        o, w = offs[i], eplan.widths[i]
        fields[spec.index] = (ebytes[:n, o:o + w], elens[:n, i])
    if not fields:
        return None
    untr = np.asarray(untrusted, dtype=np.int64) \
        if untrusted is not None else np.zeros(0, dtype=np.int64)
    return DeviceEgress(eplan.encoder, n, fields, untr)


# ---------------------------------------------------------------------------
# host twins + vectorized line assembly
# ---------------------------------------------------------------------------
#
# piece = ("const", bytes-as-uint8[k])                same bytes every row
#       | ("fixed", buf uint8[n, W], lens int32[n])   left-aligned
#       | ("var",   values uint8[total], offsets int64[n+1])
#
# A destination builds one piece per wire token (field text, separator,
# JSON key, metadata column) and `assemble_rows` copies them into one
# contiguous buffer — one C pass over the rows where the native library
# is loaded (native/framer.c `etl_assemble_rows`), and where it is not
# two cumsums and one fancy-index store per piece; no per-row Python
# either way.

def const_piece(b: bytes) -> tuple:
    return ("const", np.frombuffer(b, dtype=np.uint8))


def fixed_piece(buf: np.ndarray, lens: np.ndarray) -> tuple:
    return ("fixed", buf, lens)


def var_from_texts(items: list) -> tuple:
    """Variable piece from per-row bytes (the host per-value path)."""
    n = len(items)
    lens = np.fromiter((len(b) for b in items), dtype=np.int64, count=n)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    total = int(offs[-1])
    values = np.frombuffer(b"".join(items), dtype=np.uint8) if total \
        else np.zeros(0, dtype=np.uint8)
    return ("var", values, offs)


def patch_rows_fixed(buf: np.ndarray, lens: np.ndarray, rows: np.ndarray,
                     text: bytes) -> tuple:
    """Overwrite `rows` of a fixed piece with a short constant (NULL
    markers). Copies first: device-fetched buffers are read-only and
    lens views may be shared across columns."""
    if rows.size == 0:
        return buf, lens
    nb = np.frombuffer(text, dtype=np.uint8)
    buf = np.array(buf, copy=True)
    lens = np.array(lens, dtype=np.int64, copy=True)
    buf[rows, :nb.size] = nb
    lens[rows] = nb.size
    return buf, lens


def int_text_fixed(arr: np.ndarray) -> tuple:
    """Host twin of the device int renderers: same digits as str(int).
    One C pass where the native library is loaded (`native.
    int_text_fixed`), numpy where it is not or the dtype is not the C
    pass's: the same (uint8[n, 21] zero-padded, int64[n]) either way."""
    a = np.asarray(arr)
    done = native.int_text_fixed(a)
    return done if done is not None else _int_text_fixed_np(a)


def _int_text_fixed_np(a: np.ndarray) -> tuple:
    """`native.int_text_fixed` in numpy, for a process without the native
    library."""
    n = a.shape[0]
    if n == 0:
        return np.zeros((0, 21), dtype=np.uint8), np.zeros(0, np.int64)
    codes = np.ascontiguousarray(a.astype("U21")).view(np.uint32) \
        .reshape(n, 21)
    return codes.astype(np.uint8), \
        np.count_nonzero(codes, axis=1).astype(np.int64)


def bool_text_fixed(flags: np.ndarray) -> tuple:
    t = np.frombuffer(b"true\x00", dtype=np.uint8)
    f = np.frombuffer(b"false", dtype=np.uint8)
    m = np.asarray(flags).astype(bool)
    return np.where(m[:, None], t, f), np.where(m, 4, 5).astype(np.int64)


def date_text_fixed(days: np.ndarray) -> tuple:
    """Host twin of the device DATE renderer (in-range rows only —
    callers mask specials/out-of-range rows to the per-value oracle,
    same as the columnar encoders do)."""
    n = np.asarray(days).shape[0]
    if n == 0:
        return np.zeros((0, 10), dtype=np.uint8), np.zeros(0, np.int64)
    s = np.datetime_as_string(np.asarray(days).astype("M8[D]"), unit="D")
    codes = np.ascontiguousarray(s.astype("U10")).view(np.uint32) \
        .reshape(n, 10)
    return codes.astype(np.uint8), np.full(n, 10, dtype=np.int64)


def timestamp_text_fixed(micros: np.ndarray) -> tuple:
    """Host twin of the device TIMESTAMP renderer: always 6 fractional
    digits, 'T' replaced by a space — np.datetime_as_string(unit='us')
    exactly as the ClickHouse columnar encoder renders it."""
    n = np.asarray(micros).shape[0]
    if n == 0:
        return np.zeros((0, 26), dtype=np.uint8), np.zeros(0, np.int64)
    s = np.char.replace(
        np.datetime_as_string(np.asarray(micros, dtype=np.int64)
                              .astype("M8[us]"), unit="us"), "T", " ")
    codes = np.ascontiguousarray(s.astype("U26")).view(np.uint32) \
        .reshape(n, 26)
    return codes.astype(np.uint8), np.full(n, 26, dtype=np.int64)


def assemble_rows(n: int, pieces: list,
                  override: "dict | None" = None) -> tuple:
    """Copy `pieces` into one contiguous byte buffer, one row per
    line. `override` maps row index → full replacement bytes for that
    row (the oracle-rendered untrusted/special rows) — overridden rows
    take NO bytes from any piece. Returns (out uint8[total],
    row_offsets int64[n+1]). One C pass over the rows where the native
    library is loaded (`native.assemble_rows`), numpy where it is not:
    the same two arrays either way."""
    done = native.assemble_rows(n, pieces, override)
    registry.counter_inc(ETL_EGRESS_ASSEMBLED_ROWS_TOTAL, n)
    if done is not None:
        registry.counter_inc(ETL_EGRESS_NATIVE_ASSEMBLED_ROWS_TOTAL, n)
        return done
    return _assemble_rows_np(n, pieces, override)


def _assemble_rows_np(n: int, pieces: list,
                      override: "dict | None" = None) -> tuple:
    """`native.assemble_rows` in numpy, for a process without the native
    library: per piece some ten passes over int64 index arrays, one entry
    per output byte, where the C pass copies each row's bytes once."""
    m = len(pieces)
    L = np.zeros((n, m), dtype=np.int64)
    for j, p in enumerate(pieces):
        if p[0] == "const":
            L[:, j] = p[1].size
        elif p[0] == "fixed":
            L[:, j] = p[2]
        else:
            L[:, j] = p[2][1:] - p[2][:-1]
    if override:
        rows = np.fromiter(override.keys(), dtype=np.int64,
                           count=len(override))
        L[rows, :] = 0
    row_len = L.sum(axis=1)
    if override:
        for r, b in override.items():
            row_len[r] = len(b)
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(row_len, out=starts[1:])
    out = np.empty(int(starts[-1]), dtype=np.uint8)
    within = np.zeros(n, dtype=np.int64)
    for j, p in enumerate(pieces):
        lj = L[:, j]
        dst0 = starts[:-1] + within
        if p[0] == "const":
            c = p[1]
            if c.size:
                live = np.flatnonzero(lj) if override else None
                d = dst0[live] if live is not None else dst0
                idx = d[:, None] + np.arange(c.size, dtype=np.int64)
                out[idx.reshape(-1)] = np.tile(c, d.size)
        else:
            tot = int(lj.sum())
            if tot:
                cum_excl = np.cumsum(lj) - lj
                pos = np.arange(tot, dtype=np.int64) \
                    - np.repeat(cum_excl, lj)
                dst = np.repeat(dst0, lj) + pos
                if p[0] == "fixed":
                    buf = p[1]
                    w = buf.shape[1]
                    src = np.repeat(np.arange(n, dtype=np.int64) * w,
                                    lj) + pos
                    out[dst] = buf.reshape(-1)[src]
                else:
                    src = np.repeat(p[2][:-1], lj) + pos
                    out[dst] = p[1][src]
        within += lj
    if override:
        for r, b in override.items():
            if b:
                out[starts[r]:starts[r] + len(b)] = \
                    np.frombuffer(b, dtype=np.uint8)
    return out, starts
