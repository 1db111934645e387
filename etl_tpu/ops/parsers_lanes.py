"""Lane-packed (transposed) field parsers for the Pallas kernel.

The row-major parsers in ops/parsers.py operate on `[R, L]` byte
matrices whose minor (lane) dimension is the field width L = 1-12 —
under Mosaic every intermediate pads L to 128 lanes, wasting >90% of
the VPU (the measured 18x loss vs XLA, VERDICT r3 #8). This module is
the lane-packed redesign: each field byte POSITION is one full `[R]`
vector (R = the Pallas block's row count, a multiple of 128), so every
vector op runs on fully-populated lanes and the per-position work is a
short static Python loop over the field width.

Semantics are transcribed 1:1 from parsers.py (same component names,
same ok conditions, same CPU-fallback boundaries); the differential
suites run both engines over the same inputs and must agree bit-for-bit.
Scalar helpers (pow10 select chain, civil-date math, limb range checks)
are shared by import so the two conventions cannot drift on the math.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..models.pgtypes import CellKind
from .parsers import (COLON, D0, DASH, DOT, MINUS, PLUS, SPACE,
                      _days_from_civil_dev, _int_range_ok,
                      _nibble_to_ascii, pow10)


def _row(rows, i):
    """rows[i], or a zero vector past the gathered width (parsers.py
    indexes into the zero-padded [R, L] matrix; the transposed form must
    read the same zeros)."""
    return rows[i] if 0 <= i < len(rows) else jnp.zeros_like(rows[0])


def _at(rows, q):
    """Per-row dynamic position read: rows[q[r]][r] — the transposed
    take_along_axis, lowered as a select chain (Mosaic has no sublane
    gather)."""
    out = jnp.zeros_like(q)
    for i in range(len(rows)):
        out = jnp.where(q == i, rows[i], out)
    return out


def _true(v):
    return jnp.ones_like(v, dtype=bool)


# -- integers ---------------------------------------------------------------


def _digit_limbs_lanes(rows, lengths, start, n_limbs: int = 3):
    L = len(rows)
    all_digits = _true(lengths)
    limbs = [jnp.zeros_like(lengths) for _ in range(n_limbs)]
    for i in range(L):
        d = rows[i] - D0
        in_range = (start <= i) & (i < lengths)
        is_digit = (d >= 0) & (d <= 9)
        all_digits &= ~(in_range & ~is_digit)
        r = lengths - 1 - i
        w = pow10(r % 9)
        dd = jnp.where(in_range & is_digit, d, 0)
        k = r // 9
        for kk in range(n_limbs):
            limbs[kk] = limbs[kk] + jnp.where(in_range & (k == kk),
                                              dd * w, 0)
    return limbs, all_digits


def parse_int_lanes(rows, lengths):
    neg = rows[0] == MINUS
    plus = rows[0] == PLUS
    start = (neg | plus).astype(jnp.int32)
    limbs, all_digits = _digit_limbs_lanes(rows, lengths, start)
    ndigits = lengths - start
    ok = all_digits & (ndigits >= 1) & (ndigits <= 27) \
        & (lengths <= len(rows))
    return neg, limbs[0], limbs[1], limbs[2], ndigits, ok


def parse_bool_lanes(rows, lengths):
    t = rows[0] == ord("t")
    f = rows[0] == ord("f")
    ok = (lengths == 1) & (t | f)
    return t, ok


# -- date / time ------------------------------------------------------------


def _fixed2_lanes(rows, p):
    return (_row(rows, p) - D0) * 10 + (_row(rows, p + 1) - D0)


def parse_date_lanes(rows, lengths):
    def dig(i):
        return _row(rows, i) - D0

    y = dig(0) * 1000 + dig(1) * 100 + dig(2) * 10 + dig(3)
    m = _fixed2_lanes(rows, 5)
    dd = _fixed2_lanes(rows, 8)
    digits_ok = _true(lengths)
    for i in (0, 1, 2, 3, 5, 6, 8, 9):
        digits_ok &= (dig(i) >= 0) & (dig(i) <= 9)
    ok = (lengths == 10) & digits_ok \
        & (_row(rows, 4) == DASH) & (_row(rows, 7) == DASH) \
        & (m >= 1) & (m <= 12) & (dd >= 1) & (dd <= 31) & (y >= 1)
    days = _days_from_civil_dev(y, m, dd)
    return jnp.where(ok, days, 0), ok


def _parse_hms_at_lanes(rows, lengths, base: int):
    L = len(rows)
    hh = _fixed2_lanes(rows, base)
    mm = _fixed2_lanes(rows, base + 3)
    ss = _fixed2_lanes(rows, base + 6)
    sep_ok = (_row(rows, base + 2) == COLON) \
        & (_row(rows, base + 5) == COLON)
    digits_ok = _true(lengths)
    for i in (base, base + 1, base + 3, base + 4, base + 6, base + 7):
        d = _row(rows, i) - D0
        digits_ok &= (d >= 0) & (d <= 9)
    if base + 8 < L:
        has_dot = (lengths > base + 8) & (rows[base + 8] == DOT)
    else:
        has_dot = jnp.zeros_like(lengths, dtype=bool)

    # fractional digits: contiguous run starting at base+9, max 6
    frac_start = base + 9
    running = _true(lengths)
    run = jnp.zeros_like(lengths)
    for k in range(6):
        i = frac_start + k
        d = _row(rows, i) - D0
        in_window = (i < L) & (i < lengths)
        this = in_window & (d >= 0) & (d <= 9)
        running &= this
        run = run + running.astype(jnp.int32)
    run = jnp.where(has_dot, run, 0)
    us = jnp.zeros_like(lengths)
    for k in range(6):
        i = frac_start + k
        d = _row(rows, i) - D0
        in_window = (i < L) & (i < lengths)
        frac_digit = in_window & (d >= 0) & (d <= 9)
        us = us + jnp.where(frac_digit & (k < run), d * 10 ** (5 - k), 0)
    frac_ok = ~has_dot | (run >= 1)
    end = base + 8 + jnp.where(has_dot, 1 + run, 0)
    sec = (hh * 60 + mm) * 60 + ss
    ok = sep_ok & digits_ok & frac_ok & (hh <= 23) & (mm <= 59) & (ss <= 59)
    return sec, us, end, ok


def parse_time_lanes(rows, lengths):
    sec, us, end, ok = _parse_hms_at_lanes(rows, lengths, 0)
    ok = ok & (end == lengths)
    ms = sec * 1000 + us // 1000
    return ms, us % 1000, ok


def _parse_tz_at_lanes(rows, lengths, p):
    sign_b = _at(rows, p)
    neg = sign_b == MINUS
    sign_ok = neg | (sign_b == PLUS)
    d1, d2 = _at(rows, p + 1) - D0, _at(rows, p + 2) - D0
    hh = d1 * 10 + d2
    hh_ok = (d1 >= 0) & (d1 <= 9) & (d2 >= 0) & (d2 <= 9)
    has_min = (lengths > p + 3) & (_at(rows, p + 3) == COLON)
    m1, m2 = _at(rows, p + 4) - D0, _at(rows, p + 5) - D0
    mm = jnp.where(has_min, m1 * 10 + m2, 0)
    mm_ok = ~has_min | ((m1 >= 0) & (m1 <= 9) & (m2 >= 0) & (m2 <= 9))
    has_sec = has_min & (lengths > p + 6) & (_at(rows, p + 6) == COLON)
    s1, s2 = _at(rows, p + 7) - D0, _at(rows, p + 8) - D0
    ss = jnp.where(has_sec, s1 * 10 + s2, 0)
    ss_ok = ~has_sec | ((s1 >= 0) & (s1 <= 9) & (s2 >= 0) & (s2 <= 9))
    end = p + 3 + jnp.where(has_min, 3, 0) + jnp.where(has_sec, 3, 0)
    off = hh * 3600 + mm * 60 + ss
    off = jnp.where(neg, -off, off)
    return off, end, sign_ok & hh_ok & mm_ok & ss_ok & (hh <= 15)


def parse_timestamp_lanes(rows, lengths, with_tz: bool):
    days, date_ok = parse_date_lanes(rows[:10], jnp.full_like(lengths, 10))
    space_ok = _row(rows, 10) == SPACE
    sec, us, end, hms_ok = _parse_hms_at_lanes(rows, lengths, 11)
    if with_tz:
        tz, tz_end, tz_ok = _parse_tz_at_lanes(rows, lengths, end)
        ok = date_ok & space_ok & hms_ok & tz_ok & (tz_end == lengths)
    else:
        tz = jnp.zeros_like(sec)
        ok = date_ok & space_ok & hms_ok & (end == lengths)
    ok = ok & (lengths >= 19)
    ms = sec * 1000 + us // 1000
    return days, ms, us % 1000, tz, ok


# -- float ------------------------------------------------------------------


def parse_float_lanes(rows, lengths):
    L = len(rows)

    def match(lit: bytes):
        ok = lengths == len(lit)
        for i, ch in enumerate(lit):
            ok = ok & (_row(rows, i) == ch)
        return ok

    is_nan = match(b"NaN")
    is_pinf = match(b"Infinity")
    is_ninf = match(b"-Infinity")
    special = (is_nan * 1 + is_pinf * 2 + is_ninf * 3).astype(jnp.int32)

    neg = rows[0] == MINUS
    start = (neg | (rows[0] == PLUS)).astype(jnp.int32)

    # first 'e'/'E' position (argmax over axis 1 in the row-major form)
    e_pos = lengths
    has_e = jnp.zeros_like(lengths, dtype=bool)
    for i in reversed(range(L)):
        is_e_i = ((rows[i] == ord("e")) | (rows[i] == ord("E"))) \
            & (i < lengths)
        e_pos = jnp.where(is_e_i, i, e_pos)
        has_e = has_e | is_e_i
    # first '.' before the exponent
    dot_pos = e_pos
    has_dot = jnp.zeros_like(lengths, dtype=bool)
    n_dots = jnp.zeros_like(lengths)
    for i in reversed(range(L)):
        is_dot_i = (rows[i] == DOT) & (i < lengths) & (i < e_pos)
        dot_pos = jnp.where(is_dot_i, i, dot_pos)
        has_dot = has_dot | is_dot_i
        n_dots = n_dots + is_dot_i.astype(jnp.int32)

    frac_count = jnp.where(has_dot, e_pos - dot_pos - 1,
                           0).astype(jnp.int32)
    mant_valid = _true(lengths)
    n_mant = jnp.zeros_like(lengths)
    limb0 = jnp.zeros_like(lengths)
    limb1 = jnp.zeros_like(lengths)
    running_zero = _true(lengths)
    lead_zero_run = jnp.zeros_like(lengths)
    for i in range(L):
        d = rows[i] - D0
        is_digit = (d >= 0) & (d <= 9)
        is_dot_i = (rows[i] == DOT) & (i < lengths) & (i < e_pos)
        mant_sel = (start <= i) & (i < e_pos) & ~is_dot_i
        mant_valid &= ~(mant_sel & ~is_digit)
        n_mant = n_mant + mant_sel.astype(jnp.int32)
        r = jnp.where(i < dot_pos,
                      (dot_pos - 1 - i) + frac_count,
                      e_pos - 1 - i)
        w = pow10(r % 9)
        dd = jnp.where(mant_sel & is_digit, d, 0)
        limb0 = limb0 + jnp.where(mant_sel & (r // 9 == 0), dd * w, 0)
        limb1 = limb1 + jnp.where(mant_sel & (r // 9 == 1), dd * w, 0)
        # leading-zero run among mantissa digits (non-mantissa = neutral).
        # Plain boolean algebra: a select between i1 vectors is something
        # Mosaic (libtpu 0.0.34) refuses to truncate back from i8
        running_zero &= ~mant_sel | (d == 0)
        lead_zero_run = lead_zero_run \
            + (running_zero & mant_sel).astype(jnp.int32)

    # explicit exponent after 'e'
    exp_start = e_pos + 1
    exp_neg = has_e & (_at(rows, exp_start) == MINUS)
    exp_sign = has_e & (exp_neg | (_at(rows, exp_start) == PLUS))
    exp_d_start = exp_start + exp_sign.astype(jnp.int32)
    exp_valid = ~has_e | (lengths > exp_d_start)
    exp_val = jnp.zeros_like(lengths)
    for i in range(L):
        d = rows[i] - D0
        is_digit = (d >= 0) & (d <= 9)
        exp_sel = (exp_d_start <= i) & (i < lengths)
        exp_valid &= ~(exp_sel & ~is_digit)
        re = lengths - 1 - i
        ew = pow10(re % 9)
        exp_val = exp_val + jnp.where(exp_sel & is_digit & (re // 9 == 0),
                                      d * ew, 0)
    exp_val = jnp.where(exp_neg, -exp_val, exp_val)
    exp_val = jnp.where(has_e, exp_val, 0)

    sig = n_mant - lead_zero_run
    exp_adj = exp_val - frac_count
    fast = (sig <= 15) & (jnp.abs(exp_adj) <= 22) & (n_mant >= 1) \
        & (n_mant <= 18) & (n_dots <= 1) & mant_valid & exp_valid
    ok = fast | (special > 0)
    return neg, limb0, limb1, exp_adj, special, ok


# -- dispatch ---------------------------------------------------------------


def parse_column_lanes(kind, rows, lengths):
    """Transposed parse_column: `rows` is a tuple of int32[R] vectors
    (one per field byte position); returns ({component: int32[R]}, ok)."""
    if kind is CellKind.BOOL:
        t, ok = parse_bool_lanes(rows, lengths)
        return {"v": t.astype(jnp.int32)}, ok
    if kind in (CellKind.I16, CellKind.I32, CellKind.U32):
        neg, l0, l1, l2, nd, ok = parse_int_lanes(rows, lengths)
        ok = ok & _int_range_ok(kind, neg, l0, l1, l2, nd)
        v = l1 * jnp.int32(1_000_000_000) + l0
        return {"v": jnp.where(neg, -v, v)}, ok
    if kind is CellKind.I64:
        neg, l0, l1, l2, nd, ok = parse_int_lanes(rows, lengths)
        ok = ok & _int_range_ok(kind, neg, l0, l1, l2, nd)
        return {"neg": neg.astype(jnp.int32), "l0": l0, "l1": l1,
                "l2": l2}, ok
    if kind in (CellKind.F32, CellKind.F64):
        neg, l0, l1, ea, sp, ok = parse_float_lanes(rows, lengths)
        return {"neg": neg.astype(jnp.int32), "l0": l0, "l1": l1,
                "ea": ea, "sp": sp}, ok
    if kind is CellKind.DATE:
        days, ok = parse_date_lanes(rows, lengths)
        return {"days": days}, ok
    if kind is CellKind.TIME:
        ms, us, ok = parse_time_lanes(rows, lengths)
        return {"ms": ms, "us": us}, ok
    if kind in (CellKind.TIMESTAMP, CellKind.TIMESTAMPTZ):
        days, ms, us, tz, ok = parse_timestamp_lanes(
            rows, lengths, with_tz=kind is CellKind.TIMESTAMPTZ)
        return {"days": days, "ms": ms - tz * 1000, "us": us}, ok
    raise AssertionError(kind)


def unpack_nibbles_lanes(packed_rows, width: int):
    """Transposed unpack_nibbles: packed_rows is W/2 int32[R] vectors of
    nibble pairs; returns W ASCII int32[R] vectors (position k from the
    high nibble of row k, position k + W/2 from the low nibble)."""
    his = [_nibble_to_ascii((p >> 4) & 0xF) for p in packed_rows]
    los = [_nibble_to_ascii(p & 0xF) for p in packed_rows]
    return his + los
