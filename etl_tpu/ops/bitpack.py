"""Bit-packed device→host result transport.

Every batch pays one device→host fetch, so the decode program's output
layout decides how many bytes that fetch moves (the link's cost on this
machine: not measured). Instead of one
int32 lane per parsed component (16 B/row for a 3-int column schema), each
row's components are packed into the fewest 32-bit words that their
*maximum possible magnitudes* allow — and those maxima are known on the
host before dispatch, because a decimal field of `d` text characters can
encode at most `10^d - 1`: the per-column byte widths the host already
computes for the gather bound every component's bit width statically.

Layout (per row): for each dense column in spec order — 1 ok bit, then
each nonzero-width component (parsers.COLUMN_COMPONENTS order), signed
components zigzag-encoded. Fields straddle word boundaries; total width
rounds up to whole uint32 words. The device emits `uint32[n_words, R]`
(one fetch), the host unpacks with vectorized shifts — a few numpy ops per
component.

Components whose width bound is 0 bits (e.g. the high limb of a bigint
column whose longest text is 9 chars) are omitted entirely and substituted
as zeros on the host.

Reference parity note: the reference returns parsed values in-process
(codec/text.rs), so it has no transport layer to compare; this module is
where the TPU build pays for — and wins back — the host↔device boundary.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..models.pgtypes import CellKind
from . import parsers

# hard magnitude caps per (kind, component): the parser's ok-check bounds
# the value independently of text width (e.g. an I32 is range-checked), so
# bits never exceed these even for huge gather widths
_DAYS_ZZ_BITS = 23  # year 1..9999 → days ∈ [-719162, 2932896]; zigzag max
#                     = 5,865,792 < 2^23 — 22 bits would corrupt late dates
_MS_BITS = 27  # 0..86_399_999 ms of day
_MS_TZ_ZZ_BITS = 29  # ms shifted by ±16h tz → zigzag
_US_BITS = 10  # 0..999


def _zz_bits(vmax: int) -> int:
    """Bits for zigzag(v), |v| ≤ vmax (zigzag(-m) = 2m-1, zigzag(m) = 2m)."""
    return max(1, (2 * vmax).bit_length())


def _dec_bits(digits: int) -> int:
    """Bits for a non-negative decimal of `digits` chars."""
    if digits <= 0:
        return 0
    return (10**digits - 1).bit_length()


def component_bits(kind: CellKind, comp: str, width: int) -> tuple[int, bool]:
    """(bits, zigzag) for one component given the column's max text width.
    bits == 0 means the component is statically zero and is not packed."""
    d = width  # max text chars ⇒ max decimal digits (sign char only shrinks)
    if kind is CellKind.BOOL:
        return 1, False
    if kind is CellKind.I16:
        return min(_zz_bits(10**min(d, 5) - 1), _zz_bits(32768)), True
    if kind is CellKind.I32:
        if d >= 10:
            return 32, True  # zigzag(int32) always fits 32 bits
        return _zz_bits(10**d - 1), True
    if kind is CellKind.U32:
        return min(_dec_bits(d), 32), False
    if kind is CellKind.I64:
        if comp == "neg":
            return 1, False
        if comp == "l0":
            return _dec_bits(min(d, 9)), False
        if comp == "l1":
            return _dec_bits(min(max(d - 9, 0), 9)), False
        if comp == "l2":
            # ok requires ≤ 19 digits ⇒ top limb ≤ 9
            return (4 if d > 18 else 0), False
    if kind in (CellKind.F32, CellKind.F64):
        if comp == "neg":
            return 1, False
        if comp == "l0":
            return _dec_bits(min(d, 9)), False
        if comp == "l1":
            # mantissa digit count is capped by the parser's fast path (18);
            # limb1 holds digits 9..17 from the right
            return _dec_bits(min(max(d - 9, 0), 9)), False
        if comp == "ea":
            return _zz_bits(22), True  # |exp_adj| ≤ 22 when ok
        if comp == "sp":
            return 2, False
    if kind is CellKind.DATE:
        return _DAYS_ZZ_BITS, True
    if kind is CellKind.TIME:
        return (_MS_BITS, False) if comp == "ms" else (_US_BITS, False)
    if kind is CellKind.TIMESTAMP:
        if comp == "days":
            return _DAYS_ZZ_BITS, True
        return (_MS_BITS, False) if comp == "ms" else (_US_BITS, False)
    if kind is CellKind.TIMESTAMPTZ:
        if comp == "days":
            return _DAYS_ZZ_BITS, True
        return (_MS_TZ_ZZ_BITS, True) if comp == "ms" else (_US_BITS, False)
    raise AssertionError((kind, comp))


def saturation_width(kind: CellKind) -> int:
    """Text width beyond which the layout stops changing — bit widths are
    clamped here so drifting field lengths (e.g. suppressed trailing
    fractional-second zeros) don't multiply jit signatures for programs
    that would lower identically."""
    if kind is CellKind.BOOL:
        return 1
    if kind in (CellKind.DATE, CellKind.TIME, CellKind.TIMESTAMP,
                CellKind.TIMESTAMPTZ):
        return 1  # layout is fixed for these kinds
    if kind is CellKind.I16:
        return 5
    if kind in (CellKind.I32, CellKind.U32):
        return 10
    if kind is CellKind.I64:
        return 19
    if kind in (CellKind.F32, CellKind.F64):
        return 18
    raise AssertionError(kind)


@dataclasses.dataclass(frozen=True)
class FieldSlot:
    comp: str  # component name, or "ok"
    bit_off: int
    bits: int
    zigzag: bool


@dataclasses.dataclass(frozen=True)
class BitLayout:
    """Static packing plan for one (specs, widths) signature."""

    slots: tuple[tuple[FieldSlot, ...], ...]  # per dense column
    n_words: int
    kinds: tuple[CellKind, ...]

    @property
    def total_bits(self) -> int:
        return sum(s.bits for col in self.slots for s in col)


def layout_for_specs(specs: tuple[tuple[int, CellKind, int, int], ...]
                     ) -> BitLayout:
    """THE projection from engine 4-tuple specs (col, kind, gather_width,
    bit_width) to the packed layout. Every site that touches the packed
    words — the XLA program, the Pallas kernel, the host completion, the
    driver entry — must derive the layout through this one function;
    disagreement silently misreads columns."""
    return build_layout(tuple((i, k, bw) for i, k, _, bw in specs))


def build_layout(specs: tuple[tuple[int, CellKind, int], ...]) -> BitLayout:
    """specs: (col_index, kind, max_text_width) per dense column — the same
    tuple that keys the jit cache, so the layout is static per program."""
    cols: list[tuple[FieldSlot, ...]] = []
    off = 0
    for _, kind, width in specs:
        slots = [FieldSlot("ok", off, 1, False)]
        off += 1
        for comp in parsers.COLUMN_COMPONENTS[kind]:
            bits, zz = component_bits(kind, comp, width)
            if bits == 0:
                continue
            slots.append(FieldSlot(comp, off, bits, zz))
            off += bits
        cols.append(tuple(slots))
    return BitLayout(tuple(cols), max(1, -(-off // 32)),
                     tuple(k for _, k, _ in specs))


def pack_device(layout: BitLayout, columns) -> jnp.ndarray:
    """Pack per-column (ok, comps) into uint32[n_words, R] on device.

    `columns`: list aligned with layout.slots of (ok_bool[R], comps dict
    name→int32[R]). Pure elementwise uint32 shifts/ors — fuses into the
    parse program, nothing extra materializes in HBM.
    """
    R = columns[0][0].shape[0]
    words = [jnp.zeros(R, dtype=jnp.uint32) for _ in range(layout.n_words)]
    for (ok, comps), slots in zip(columns, layout.slots):
        for s in slots:
            if s.comp == "ok":
                v = ok.astype(jnp.uint32)
            else:
                raw = comps[s.comp].astype(jnp.int32)
                if s.zigzag:
                    raw = (raw << 1) ^ (raw >> 31)
                v = raw.astype(jnp.uint32)
            if s.bits < 32:
                v = v & jnp.uint32((1 << s.bits) - 1)
            w, sh = divmod(s.bit_off, 32)
            words[w] = words[w] | (v << sh)
            if sh + s.bits > 32:
                words[w + 1] = words[w + 1] | (v >> (32 - sh))
    return jnp.stack(words, axis=0)


def compact_packed(words, keep, n_shards: int):
    """In-program row compaction: scatter the kept rows of
    `words` uint32[n_words, R] to the FRONT of their shard block via an
    exclusive prefix sum over the keep mask — filtered rows never reach
    the HBM output buffer positions the host fetches.

    Shard-local by construction: rows reshape to [n_shards, R/n_shards]
    exactly along the mesh's block sharding, the cumsum runs inside each
    shard, and every kept row's destination stays inside its own block —
    zero cross-device collectives on the forward path, matching the
    unfiltered program's contract. Single-device callers pass n_shards=1
    (one global block).

    Returns (words_compacted, keep_mask uint32[⌈R/32⌉] — the keep bits
    packed 32/word, little bit order, counts int32[n_shards]). The host
    reconstructs survivor row indices from the mask (compaction is
    stable, so survivors are exactly the set bit positions in ascending
    order) at 1 BIT per staged row of fetch — against 32 bits a rowid
    vector would cost. On a single device the words fetch is then sized
    to the survivor count (engine._complete_filtered): fetched bytes
    scale with selectivity, not batch size."""
    R = keep.shape[0]
    rps = R // n_shards
    k2 = keep.astype(jnp.int32).reshape(n_shards, rps)
    pos = jnp.cumsum(k2, axis=1) - k2  # exclusive prefix sum, shard-local
    counts = k2.sum(axis=1, dtype=jnp.int32)
    # dropped rows scatter to index rps, which mode="drop" discards. The
    # scatter is BATCHED per shard block (vmap over the leading shard
    # axis) with block-LOCAL destination indices: GSPMD partitions the
    # batched scatter along 'sp' with no communication. The previous
    # formulation scattered through a single GLOBAL dest vector, which
    # the partitioner could not prove block-diagonal — it all-gathered
    # the full words array around the scatter on every mesh dispatch
    # (caught by the etl-lint ir-collective contract).
    dest_local = jnp.where(k2 > 0, pos, rps)
    w3 = words.reshape(words.shape[0], n_shards, rps).transpose(1, 0, 2)
    blocks = jax.vmap(
        lambda w, d: jnp.zeros_like(w).at[:, d].set(w, mode="drop"))(
            w3, dest_local)
    words_c = blocks.transpose(1, 0, 2).reshape(words.shape)
    pad = (-R) % 32
    bits = keep
    if pad:
        bits = jnp.concatenate(
            [keep, jnp.zeros((pad,), dtype=keep.dtype)])
    bits32 = bits.astype(jnp.uint32).reshape(-1, 32)
    mask = (bits32 << jnp.arange(32, dtype=jnp.uint32)[None, :]) \
        .sum(axis=1, dtype=jnp.uint32)
    return words_c, mask, counts


def unpack_keep_mask(mask: np.ndarray, n_rows: int) -> np.ndarray:
    """Host half of compact_packed's mask transport: set-bit positions →
    survivor row indices, ascending (== compaction order)."""
    bits = np.unpackbits(np.ascontiguousarray(mask).view(np.uint8),
                         bitorder="little")[:n_rows]
    return np.flatnonzero(bits).astype(np.int64)


def parse_and_pack(bmat, lengths, specs, nibble: bool,
                   n_shards: int | None = None,
                   pred=None, row_flags=None):
    """THE device program body shared by the XLA path and the Pallas
    kernel: per-column parse (parsers.parse_column) + bit-pack
    (pack_device). One definition — a divergence between the two lowering
    paths would silently corrupt columns.

    With `n_shards` (the mesh path: rows block-sharded over 'sp'), also
    returns int32[n_shards] per-shard counts of fallback-CANDIDATE rows —
    rows where some nonempty field failed its device parse — reduced ON
    DEVICE inside each row shard (the reshape groups rows exactly along
    the block sharding, so XLA keeps the reduction shard-local). Zero-
    length fields are not failures (NULL / TOAST / the all-NULL padding
    rows pad_to_multiple appends), so padding never inflates the counts.
    The host aggregates these for shard-health telemetry only: the exact
    per-row fallback set still comes from the unpacked ok bits masked by
    host-side validity (a zero-length field of a non-null row IS a real
    fallback there, invisible to this length-gated device mask).

    With `pred` (a predicate.CompiledRowFilter — the fused publication
    row filter), the predicate evaluates over the ALREADY-PARSED int32
    components (no re-parse, no extra HBM traffic: the values are in
    registers between parse and pack) and survivors compact to the front
    of their shard block (`compact_packed`). `row_flags` uint8[R] carries
    the host's per-row disposition (0 dead padding / 1 live / 2 live +
    force-keep). Returns (words_compacted, keep_mask,
    counts[, shard_bad]).
    The XLA path and the Pallas kernel share the predicate evaluator and
    the compaction epilogue, so the two engines' compacted outputs are
    byte-identical by construction — `jnp.where`-mask evaluation here is
    the differential twin of the in-kernel keep computation."""
    layout = layout_for_specs(specs)
    columns = []
    row_ok = None
    colmap: dict = {}
    ref_cols = frozenset(pred.referenced_indices) if pred is not None \
        else frozenset()
    w_off = 0
    # one named scope per stage: each fusion's metadata in the device
    # trace then says which stage (and which column kind's parser) it is
    for j, (col_idx, kind, width, _bw) in enumerate(specs):
        with jax.named_scope("gather"):
            if nibble:
                packed = bmat[:, w_off // 2 : (w_off + width) // 2]
                b = parsers.unpack_nibbles(packed, width)
            else:
                b = bmat[:, w_off : w_off + width].astype(jnp.int32)
        w_off += width
        with jax.named_scope(f"parse_{kind.name.lower()}"):
            comp, ok = parsers.parse_column(kind, b, lengths[:, j])
        columns.append((ok, comp))
        if col_idx in ref_cols:
            colmap[col_idx] = (comp, ok, lengths[:, j] == 0)
        if n_shards is not None:
            col_ok = ok | (lengths[:, j] == 0)
            row_ok = col_ok if row_ok is None else (row_ok & col_ok)
    with jax.named_scope("bitpack"):
        words = pack_device(layout, columns)
    if pred is not None:
        with jax.named_scope("compact"):
            keep = pred.device_keep(colmap, row_flags.astype(jnp.int32))
            words_c, mask, counts = compact_packed(words, keep,
                                                   n_shards or 1)
        if n_shards is None:
            return words_c, mask, counts
    if n_shards is None:
        return words
    nonempty = (lengths > 0).any(axis=1)
    bad = jnp.zeros_like(nonempty) if row_ok is None \
        else ((~row_ok) & nonempty)
    shard_bad = bad.reshape(n_shards, -1).sum(axis=1, dtype=jnp.int32)
    if pred is not None:
        return words_c, mask, counts, shard_bad
    return words, shard_bad


def unpack_host(layout: BitLayout, words: np.ndarray, col: int,
                n: int) -> tuple[np.ndarray, list[np.ndarray]]:
    """Extract (ok bool[n], components as int64[n] in COLUMN_COMPONENTS
    order, zeros substituted for omitted ones) for dense column `col` from
    fetched uint32[n_words, R]."""
    kind = layout.kinds[col]
    slots = {s.comp: s for s in layout.slots[col]}

    def get(s: FieldSlot) -> np.ndarray:
        w, sh = divmod(s.bit_off, 32)
        if sh + s.bits <= 32:
            v = (words[w, :n] >> np.uint32(sh)).astype(np.uint64)
        else:
            v = ((words[w, :n].astype(np.uint64) >> np.uint64(sh))
                 | (words[w + 1, :n].astype(np.uint64) << np.uint64(32 - sh)))
        v &= np.uint64((1 << s.bits) - 1)
        u = v.astype(np.int64)
        if s.zigzag:
            u = (u >> 1) ^ -(u & 1)
        return u

    ok = get(slots["ok"]).astype(np.bool_)
    comps = []
    for name in parsers.COLUMN_COMPONENTS[kind]:
        s = slots.get(name)
        comps.append(get(s) if s is not None
                     else np.zeros(n, dtype=np.int64))
    return ok, comps
