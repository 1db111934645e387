"""The C assembly of a columnar write's body against its numpy twin.

`ops/egress.assemble_rows` and `ops/egress.int_text_fixed` run one C pass
where the native library is loaded (native/framer.c `etl_assemble_rows`,
`etl_int_text_fixed`) and their numpy bodies where it is not. The numpy
bodies are the reference: here the C pass has to return the same arrays,
value for value and dtype for dtype, on every kind of piece, size, view
and override the destinations hand over. The destinations' own identity
tests (tests/test_device_egress.py) run under both branches.
"""

from __future__ import annotations

import numpy as np
import pytest

from etl_tpu import native
from etl_tpu.ops import egress as eg

SIZES = (0, 1, 500, 16_384)


@pytest.fixture(autouse=True)
def _native_loaded():
    if not native.native_available():
        pytest.skip(f"no native library: {native._build_error}")


def _counted():
    from etl_tpu.telemetry.metrics import (
        ETL_EGRESS_ASSEMBLED_ROWS_TOTAL,
        ETL_EGRESS_NATIVE_ASSEMBLED_ROWS_TOTAL, registry)

    return np.array([
        registry.get_counter(ETL_EGRESS_ASSEMBLED_ROWS_TOTAL),
        registry.get_counter(ETL_EGRESS_NATIVE_ASSEMBLED_ROWS_TOTAL)])


def _same(got, want):
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape, \
            (g.dtype, g.shape, w.dtype, w.shape)
        assert np.array_equal(g, w)


def _check(n, pieces, override=None):
    """Both branches on one piece table; returns the body."""
    want = eg._assemble_rows_np(n, pieces, override)
    got = native.assemble_rows(n, pieces, override)
    _same(got, want)
    _same(eg.assemble_rows(n, pieces, override), want)
    return got[0].tobytes()


def _lens(rng, n, hi, zero_share=0.2):
    lens = rng.integers(0, hi + 1, n)
    lens[rng.random(n) < zero_share] = 0  # zero-length fields
    return lens.astype(np.int64)


def _const(rng, size=None):
    return eg.const_piece(rng.bytes(rng.integers(0, 9)
                                    if size is None else size))


def _fixed(rng, n, w=21):
    buf = rng.integers(1, 256, (n, w), dtype=np.uint8)
    return eg.fixed_piece(buf, _lens(rng, n, w))


def _var(rng, n, hi=40, first=0, dtype=np.int64):
    lens = _lens(rng, n, hi)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    # bytes before the first offset and after the last belong to no row
    values = rng.integers(1, 256, first + int(offs[-1]) + 3, dtype=np.uint8)
    return ("var", values, (offs + first).astype(dtype))


def _mixed(rng, n):
    return [_const(rng, 1), _fixed(rng, n), _const(rng, 1), _var(rng, n),
            _const(rng), _fixed(rng, n, 50), _var(rng, n, 84), _const(rng, 0),
            _const(rng, 2)]


class TestAssembleRows:
    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("kind", ["const", "fixed", "var", "mixed"])
    def test_each_piece_kind(self, kind, n):
        rng = np.random.default_rng(n + 7)
        pieces = {"const": lambda: [_const(rng, 5)],
                  "fixed": lambda: [_fixed(rng, n)],
                  "var": lambda: [_var(rng, n)],
                  "mixed": lambda: _mixed(rng, n)}[kind]()
        _check(n, pieces)

    def test_no_pieces(self):
        assert _check(3, []) == b""

    def test_all_zero_lengths(self):
        n = 17
        pieces = [eg.const_piece(b""),
                  eg.fixed_piece(np.ones((n, 4), np.uint8),
                                 np.zeros(n, np.int64)),
                  ("var", np.zeros(0, np.uint8), np.zeros(n + 1, np.int64))]
        assert _check(n, pieces) == b""

    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("dtype", [np.int32, np.int64])
    @pytest.mark.parametrize("first", [0, 11])
    def test_var_offsets(self, first, dtype, n):
        """Arrow hands over int32 offsets, and a sliced array a first
        offset that is not 0."""
        rng = np.random.default_rng(n + first)
        _check(n, [_var(rng, n, first=first, dtype=dtype), _const(rng, 1)])

    def test_var_offsets_strided(self):
        rng = np.random.default_rng(3)
        _, values, offs = _var(rng, 64)
        wide = np.zeros((65, 2), dtype=np.int64)
        wide[:, 0] = offs
        _check(64, [("var", values, wide[:, 0])])

    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("view", [
        "read_only", "column_range", "every_other_row", "reversed_rows",
        "transposed", "broadcast_row", "int32_lens", "strided_lens",
        "spare_rows"])
    def test_fixed_views(self, view, n):
        """What a destination hands over without a copy: device-fetched
        buffers (read-only, a column range of a wider buffer, int32
        lengths cut from a matrix) and numpy views of any layout."""
        rng = np.random.default_rng(n + 31)
        w = 12
        lens = _lens(rng, n, w)
        base = rng.integers(1, 256, (2 * n + 2, 3 * w), dtype=np.uint8)
        if view == "read_only":
            buf = base[:n, :w].copy()
            buf.flags.writeable = False
            lens.flags.writeable = False
        elif view == "column_range":
            buf = base[:n, w:2 * w]
        elif view == "every_other_row":
            buf = base[:2 * n:2, :w]
        elif view == "reversed_rows":
            buf = base[:n, :w][::-1]
        elif view == "transposed":
            buf = np.asfortranarray(base[:n, :w])
        elif view == "broadcast_row":
            buf = np.broadcast_to(base[0, :w], (n, w))
        elif view == "int32_lens":
            buf, lens = base[:n, :w], lens.astype(np.int32)
        elif view == "strided_lens":
            buf = base[:n, :w]
            wide = np.zeros((n, 3), dtype=np.int32)
            wide[:, 1] = lens
            lens = wide[:, 1]
        else:
            buf = base[:, :w]  # more rows than the write has
        _check(n, [_const(rng, 1), eg.fixed_piece(buf, lens), _const(rng, 1)])

    @pytest.mark.parametrize("n", [1, 500])
    def test_null_patched_fixed(self, n):
        """`patch_rows_fixed` copies a read-only buffer and writes the
        NULL marker over some rows."""
        rng = np.random.default_rng(n)
        buf, lens = eg.int_text_fixed(
            rng.integers(-10**9, 10**9, n).astype(np.int32))
        buf.flags.writeable = False
        rows = np.flatnonzero(rng.random(n) < 0.3)
        pbuf, plens = eg.patch_rows_fixed(buf, lens, rows, b"\\N")
        body = _check(n, [eg.fixed_piece(pbuf, plens), eg.const_piece(b"\n")])
        assert body.count(b"\\N\n") == rows.size

    @pytest.mark.parametrize("n", [1, 500, 16_384])
    @pytest.mark.parametrize("which", [
        "first", "last", "adjacent", "scattered", "all", "empty_text",
        "unsorted_keys"])
    def test_override(self, which, n):
        rng = np.random.default_rng(n + 5)
        rows = {"first": [0], "last": [n - 1],
                "adjacent": [r for r in (3, 4, 5, 6) if r < n] or [0],
                "scattered": sorted({int(r) for r in
                                     rng.integers(0, n, max(n // 50, 1))}),
                "all": list(range(n)), "empty_text": [0, n - 1, n // 2],
                "unsorted_keys": sorted({0, n - 1, n // 2, n // 3})[::-1]
                }[which]
        override = {r: (b"" if which == "empty_text" and r == n // 2
                        else b"<row %d>\n" % r) for r in rows}
        pieces = _mixed(rng, n)
        body = _check(n, pieces, override)
        out, starts = native.assemble_rows(n, pieces, override)
        for r, text in override.items():
            assert out[starts[r]:starts[r + 1]].tobytes() == text
        if which == "all":
            assert body == b"".join(override[r] for r in range(n))

    def test_empty_override_is_none(self):
        rng = np.random.default_rng(9)
        pieces = _mixed(rng, 40)
        assert _check(40, pieces, {}) == _check(40, pieces, None)

    def test_override_outside_the_rows(self):
        pieces = [eg.const_piece(b"x")]
        for r in (-1, 4):
            with pytest.raises(IndexError):
                native.assemble_rows(4, pieces, {r: b"y"})

    @pytest.mark.parametrize("fault", [
        "length_over_width", "negative_length", "offsets_fall",
        "offsets_past_values", "short_lens", "short_offsets",
        "buffer_short_of_rows", "buffer_not_bytes"])
    def test_refuses_what_does_not_describe_its_bytes(self, fault):
        """The numpy twin would read a neighbour's bytes; the C pass
        stops, and the destinations fall back to their row path."""
        n = 4
        buf = np.full((n, 3), 65, dtype=np.uint8)
        lens = np.array([1, 2, 3, 0], dtype=np.int64)
        values = np.full(6, 66, dtype=np.uint8)
        offs = np.array([0, 1, 3, 6, 6], dtype=np.int64)
        if fault == "length_over_width":
            lens[1] = 4
        elif fault == "negative_length":
            lens[2] = -1
        elif fault == "offsets_fall":
            offs[2] = 0
        elif fault == "offsets_past_values":
            offs[3:] = 7
        elif fault == "short_lens":
            lens = lens[:3]
        elif fault == "short_offsets":
            offs = offs[:4]
        elif fault == "buffer_short_of_rows":
            buf = buf[:3]
        else:
            buf = buf.astype(np.int32)
        with pytest.raises(ValueError):
            native.assemble_rows(n, [eg.fixed_piece(buf, lens),
                                     ("var", values, offs)], None)

    def test_counts_rows_once_a_call(self):
        before = _counted()
        eg.assemble_rows(123, [eg.const_piece(b"ab")], None)
        assert (_counted() - before).tolist() == [123, 123]

    def test_numpy_branch_counts_no_native_rows(self, monkeypatch):
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_build_error", "test: no compiler")
        assert not native.native_available()
        before = _counted()
        out, starts = eg.assemble_rows(7, [eg.const_piece(b"ab")], None)
        assert out.tobytes() == b"ab" * 7 and starts[-1] == 14
        buf, lens = eg.int_text_fixed(np.array([-5, 12], dtype=np.int64))
        assert buf[0, :2].tobytes() == b"-5" and lens.tolist() == [2, 2]
        assert (_counted() - before).tolist() == [7, 0]


def _edges(dtype):
    """0, ±1, every power of ten ±1 and the type's extremes."""
    info = np.iinfo(dtype)
    vals = {0, 1, -1, info.min, info.max, info.min + 1, info.max - 1}
    p = 10
    while p - 1 <= info.max:
        vals.update((p - 1, p, p + 1, -p + 1, -p, -p - 1))
        p *= 10
    return sorted(v for v in vals if info.min <= v <= info.max)


class TestIntTextFixed:
    @pytest.mark.parametrize("dtype", [np.int16, np.int32, np.uint32,
                                       np.int64])
    def test_edges(self, dtype):
        vals = _edges(dtype)
        arr = np.array(vals, dtype=dtype)
        got = native.int_text_fixed(arr)
        _same(got, eg._int_text_fixed_np(arr))
        _same(eg.int_text_fixed(arr), got)
        buf, lens = got
        assert buf.shape == (len(vals), 21) and lens.dtype == np.int64
        for i, v in enumerate(vals):
            text = str(v).encode()
            assert buf[i].tobytes() == text.ljust(21, b"\0"), v
            assert lens[i] == len(text)

    def test_int64_min(self):
        buf, lens = native.int_text_fixed(
            np.array([np.iinfo(np.int64).min], dtype=np.int64))
        assert buf[0, :lens[0]].tobytes() == b"-9223372036854775808"

    @pytest.mark.parametrize("dtype", [np.int16, np.int32, np.uint32,
                                       np.int64])
    @pytest.mark.parametrize("n", SIZES)
    def test_random(self, n, dtype):
        info = np.iinfo(dtype)
        rng = np.random.default_rng(n)
        arr = rng.integers(info.min, info.max, n, dtype=dtype, endpoint=True)
        _same(native.int_text_fixed(arr), eg._int_text_fixed_np(arr))

    def test_strided_and_read_only(self):
        wide = np.arange(-30, 30, dtype=np.int32).reshape(20, 3)
        col = wide[:, 1]
        col.flags.writeable = False
        _same(native.int_text_fixed(col),
              eg._int_text_fixed_np(np.ascontiguousarray(col)))

    @pytest.mark.parametrize("dtype", [np.int8, np.uint16, np.uint64,
                                       np.dtype(">i4")])
    def test_other_dtypes_take_the_numpy_body(self, dtype):
        arr = np.array([0, 1, 100], dtype=dtype)
        assert native.int_text_fixed(arr) is None
        buf, lens = eg.int_text_fixed(arr)
        assert [buf[i, :lens[i]].tobytes() for i in range(3)] \
            == [b"0", b"1", b"100"]
