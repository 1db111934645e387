"""Native host components: the pgoutput framer, the CopyData block scan,
the COPY chunk scan and the assembly of a columnar write's body (C, via
ctypes).

Builds `framer.c` with the system compiler on first import (cached as
`_framer-<hash>.so`); falls back to a pure-Python walker with identical
outputs when no compiler is available. `frame_pgoutput` is the framer's
entry point (see ops/wal.py for the staging layer that consumes it);
`scan_copy_data` is the COPY stream's (postgres/wire.py `copy_out`);
`scan_copy_chunk` is the copy staging's (ops/staging.py `stage_copy_chunk`);
`assemble_rows` and `int_text_fixed` are the destinations' line assembly
(ops/egress.py, under the same names).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

_DIR = Path(__file__).resolve().parent

FLAG_VALUE, FLAG_NULL, FLAG_TOAST, FLAG_BINARY = 0, 1, 2, 3
# why scan_copy_data stopped (framer.c): the block ended; a message the
# per-message logic has to read
COPY_SCAN_MORE, COPY_SCAN_SLOW = 0, 1
# what scan_copy_chunk found (framer.c): well-formed rows; a delimiter
# count that is not rows x columns; the count right and a row ragged
COPY_STAGE_OK, COPY_STAGE_COUNT, COPY_STAGE_RAGGED = 0, 1, 2
_COPY_STAGE_FULL = 3  # more rows than the outputs it was given hold
# the piece kinds of assemble_rows and the integer kinds of int_text_fixed
# (framer.c); INT_TEXT_WIDTH bytes hold every int64
_PIECE_KINDS = {"const": 0, "fixed": 1, "var": 2}
_INT_TEXT_KINDS = {np.dtype(np.int16): 0, np.dtype(np.int32): 1,
                   np.dtype(np.uint32): 2, np.dtype(np.int64): 3}
INT_TEXT_WIDTH = 21

_lib = None
_build_error: str | None = None


def _load() -> ctypes.CDLL | None:
    global _lib, _build_error
    if _lib is not None or _build_error is not None:
        return _lib
    src = _DIR / "framer.c"
    tag = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    # sanitizer harness hook (scripts/sanitize_framer.py): point the
    # loader at a prebuilt instrumented .so instead of the -O3 build
    override = os.environ.get("ETL_NATIVE_FRAMER_SO")
    so = Path(override) if override else _DIR / f"_framer-{tag}.so"
    try:
        if not so.exists():
            if override:
                raise FileNotFoundError(override)
            cc = os.environ.get("CC", "cc")
            subprocess.run(
                [cc, "-O3", "-shared", "-fPIC", str(src), "-o", str(so)],
                check=True, capture_output=True, timeout=120)
            # builds of earlier framer.c revisions: nothing loads them
            # again, and a copied tree should carry one library
            for stale in _DIR.glob("_framer-*.so"):
                if stale != so:
                    stale.unlink(missing_ok=True)
        lib = ctypes.CDLL(str(so))
        lib.etl_frame_pgoutput.restype = ctypes.c_int64
        lib.etl_pack_bmat.restype = None
        lib.etl_pack_bmat.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,  # data, data_len
            ctypes.c_void_p, ctypes.c_void_p,  # offsets, lengths [R,C]
            ctypes.c_int64, ctypes.c_int32,  # n_rows, n_cols
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,  # cols,widths,n
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,  # bmat,tw,lens
        ]
        lib.etl_pack_bmat_nibble.restype = None
        lib.etl_pack_bmat_nibble.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
            ctypes.c_void_p,  # bad_rows
        ]
        lib.etl_gather_string.restype = ctypes.c_int64
        lib.etl_gather_string.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,  # off,len,valid
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,  # R, C, col
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,  # aoff,vals,cap
        ]
        lib.etl_frame_pgoutput.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,  # buf, buf_len
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,  # msg_off/len/n
            ctypes.c_int32,  # n_cols
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,  # kind/relid/oldkind
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,  # new off/len/flag
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,  # old off/len/flag
        ]
        lib.etl_scan_copy_data.restype = ctypes.c_int32
        lib.etl_scan_copy_data.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,  # buf, buf_len
            ctypes.c_void_p, ctypes.c_void_p,  # out, res[3]
        ]
        lib.etl_stage_copy_chunk.restype = ctypes.c_int32
        lib.etl_stage_copy_chunk.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,  # buf, buf_len
            ctypes.c_int32, ctypes.c_int64,  # n_cols, max_rows
            ctypes.c_void_p, ctypes.c_void_p,  # offsets, lengths [max,C]
            ctypes.c_void_p, ctypes.c_void_p,  # nulls [max,C], fallback
            ctypes.c_void_p,  # res[3]
        ]
        lib.etl_int_text_fixed.restype = ctypes.c_int32
        lib.etl_int_text_fixed.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,  # vals, n, kind
            ctypes.c_void_p, ctypes.c_void_p,  # buf [n,21], lens [n]
        ]
        lib.etl_assemble_rows.restype = ctypes.c_int64
        lib.etl_assemble_rows.argtypes = [
            ctypes.c_int64, ctypes.c_int32,  # n, n_pieces
            ctypes.c_void_p, ctypes.c_void_p,  # kind, data [n_pieces]
            ctypes.c_void_p, ctypes.c_void_p,  # aux, width [n_pieces]
            ctypes.c_int64, ctypes.c_void_p,  # n_over, over_rows
            ctypes.c_char_p, ctypes.c_void_p,  # over_data, over_off
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,  # out,cap,rows
        ]
        _lib = lib
    except Exception as e:  # pragma: no cover - depends on toolchain
        _build_error = f"{type(e).__name__}: {e}"
        _lib = None
    return _lib


def native_available() -> bool:
    return _load() is not None


def _ptr(a: np.ndarray) -> ctypes.c_void_p:
    return a.ctypes.data_as(ctypes.c_void_p)


class FramedBatch:
    """Output of the framer over n messages (see framer.c doc comment)."""

    __slots__ = ("buf", "kind", "relid", "old_kind", "new_off", "new_len",
                 "new_flag", "old_off", "old_len", "old_flag", "n_msgs")

    def __init__(self, buf: np.ndarray, n_msgs: int, n_cols: int):
        self.buf = buf
        self.n_msgs = n_msgs
        self.kind = np.zeros(n_msgs, dtype=np.uint8)
        self.relid = np.zeros(n_msgs, dtype=np.int32)
        self.old_kind = np.zeros(n_msgs, dtype=np.uint8)
        shape = (n_msgs, n_cols)
        self.new_off = np.zeros(shape, dtype=np.int32)
        self.new_len = np.zeros(shape, dtype=np.int32)
        self.new_flag = np.full(shape, FLAG_NULL, dtype=np.uint8)
        self.old_off = np.zeros(shape, dtype=np.int32)
        self.old_len = np.zeros(shape, dtype=np.int32)
        self.old_flag = np.full(shape, FLAG_NULL, dtype=np.uint8)


def frame_pgoutput(buf: bytes | np.ndarray, msg_off: np.ndarray,
                   msg_len: np.ndarray, n_cols: int) -> tuple[FramedBatch, int]:
    """Frame `len(msg_off)` pgoutput messages inside `buf`.

    Returns (framed, first_bad_index) — first_bad_index is -1 when every
    message framed cleanly; otherwise framing stopped there and the caller
    falls back to the CPU decoder for the remainder.
    """
    data = np.frombuffer(buf, dtype=np.uint8) if isinstance(buf, (bytes, bytearray)) \
        else np.ascontiguousarray(buf, dtype=np.uint8)
    msg_off = np.ascontiguousarray(msg_off, dtype=np.int64)
    msg_len = np.ascontiguousarray(msg_len, dtype=np.int32)
    n = len(msg_off)
    out = FramedBatch(data, n, n_cols)
    lib = _load()
    if lib is not None:
        p = _ptr
        bad = lib.etl_frame_pgoutput(
            p(data), len(data), p(msg_off), p(msg_len), n, n_cols,
            p(out.kind), p(out.relid), p(out.old_kind),
            p(out.new_off), p(out.new_len), p(out.new_flag),
            p(out.old_off), p(out.old_len), p(out.old_flag))
        return out, int(bad)
    return _frame_py(data, msg_off, msg_len, n_cols, out)


def _frame_py(data: np.ndarray, msg_off: np.ndarray, msg_len: np.ndarray,
              n_cols: int, out: FramedBatch) -> tuple[FramedBatch, int]:
    """Pure-Python fallback with identical semantics to framer.c."""
    import struct

    buf = data.tobytes()

    def walk(pos: int, end: int, row: int, off, ln, fl) -> int:
        if pos + 2 > end:
            return -1
        ncols = struct.unpack_from(">h", buf, pos)[0]
        pos += 2
        if ncols != n_cols:
            return -1
        for c in range(ncols):
            if pos + 1 > end:
                return -1
            k = buf[pos]
            pos += 1
            if k == ord("n"):
                fl[row, c] = FLAG_NULL
            elif k == ord("u"):
                fl[row, c] = FLAG_TOAST
            elif k in (ord("t"), ord("b")):
                if pos + 4 > end:
                    return -1
                vlen = struct.unpack_from(">i", buf, pos)[0]
                pos += 4
                if vlen < 0 or pos + vlen > end:
                    return -1
                off[row, c] = pos
                ln[row, c] = vlen
                fl[row, c] = FLAG_VALUE if k == ord("t") else FLAG_BINARY
                pos += vlen
            else:
                return -1
        return pos

    for i in range(len(msg_off)):
        pos = int(msg_off[i])
        end = pos + int(msg_len[i])
        if end > len(buf) or msg_len[i] < 1:
            return out, i
        tag = buf[pos]
        out.kind[i] = tag
        if tag == ord("I"):
            if pos + 6 > end or buf[pos + 5] != ord("N"):
                out.kind[i] = 0
                return out, i
            out.relid[i] = struct.unpack_from(">I", buf, pos + 1)[0]
            if walk(pos + 6, end, i, out.new_off, out.new_len,
                    out.new_flag) < 0:
                out.kind[i] = 0
                return out, i
        elif tag == ord("U"):
            if pos + 6 > end:
                out.kind[i] = 0
                return out, i
            out.relid[i] = struct.unpack_from(">I", buf, pos + 1)[0]
            pos += 5
            marker = buf[pos]
            if marker in (ord("O"), ord("K")):
                out.old_kind[i] = marker
                pos = walk(pos + 1, end, i, out.old_off, out.old_len,
                           out.old_flag)
                if pos < 0 or pos + 1 > end:
                    out.kind[i] = 0
                    return out, i
                marker = buf[pos]
            if marker != ord("N"):
                out.kind[i] = 0
                return out, i
            if walk(pos + 1, end, i, out.new_off, out.new_len,
                    out.new_flag) < 0:
                out.kind[i] = 0
                return out, i
        elif tag == ord("D"):
            if pos + 6 > end or buf[pos + 5] not in (ord("O"), ord("K")):
                out.kind[i] = 0
                return out, i
            out.relid[i] = struct.unpack_from(">I", buf, pos + 1)[0]
            out.old_kind[i] = buf[pos + 5]
            if walk(pos + 6, end, i, out.old_off, out.old_len,
                    out.old_flag) < 0:
                out.kind[i] = 0
                return out, i
    return out, -1


def scan_copy_data(block: bytes) -> tuple[bytes, int, int, int]:
    """Take the run of CopyData messages at the head of `block`.

    Returns (payloads, consumed, messages, stop): the payloads of the run
    joined, headers dropped; the bytes of `block` they took, which is the
    offset of the first message not taken; how many there were; and why
    the scan stopped there — COPY_SCAN_MORE (the block ends at or inside
    that message) or COPY_SCAN_SLOW (another tag, or a length outside
    4..1 GB + 4: the per-message reader's to judge).

    Runs on the event loop, so it never builds the library: the caller
    has loaded it off the loop (`native_available()`; wire.py does when a
    connection opens)."""
    lib = _lib
    if lib is None:
        assert _build_error is not None, \
            "native_available() first, off the event loop"
        return _scan_copy_data_py(block)
    out = ctypes.create_string_buffer(len(block))
    res = (ctypes.c_int64 * 3)()
    stop = lib.etl_scan_copy_data(block, len(block), out, res)
    return ctypes.string_at(out, res[1]), res[0], res[2], stop


def _scan_copy_data_py(block: bytes) -> tuple[bytes, int, int, int]:
    """Pure-Python fallback with identical outputs to framer.c."""
    pos, end, parts, stop = 0, len(block), [], COPY_SCAN_MORE
    while pos < end:
        if block[pos] != 0x64:  # 'd'
            stop = COPY_SCAN_SLOW
            break
        if pos + 5 > end:
            break
        payload = int.from_bytes(block[pos + 1:pos + 5], "big",
                                 signed=True) - 4
        if payload < 0 or payload > 1 << 30:
            stop = COPY_SCAN_SLOW
            break
        if pos + 5 + payload > end:
            break
        parts.append(block[pos + 5:pos + 5 + payload])
        pos += 5 + payload
    return b"".join(parts), pos, len(parts), stop


def scan_copy_chunk(chunk: bytes, n_cols: int):
    """One C pass over a newline-terminated chunk of COPY text rows.

    Returns (status, n_rows, n_delims, offsets, lengths, nulls, fallback),
    or None where the library is not loaded: the caller then runs its
    numpy twin, which gives the same. `status` is COPY_STAGE_OK, _COUNT or
    _RAGGED; `n_rows` and `n_delims` count the chunk's newlines and its
    tabs and newlines. On OK the first `n_rows` rows of int32 / int32 /
    bool [rows, n_cols] hold each field's start, length (0 where NULL) and
    whether it is a bare \\N, and `fallback` the ascending int64 rows that
    hold any other backslash; the caller cuts the matrices to the rows it
    keeps (`ndarray.resize`, in place).

    The scan counts the rows as it goes, so its outputs are sized first
    from a guess — the newlines in the chunk's first 8 KiB, scaled to its
    length, and a quarter more — and, where the scan runs out of them
    (rows that get much shorter further on), from a bound it cannot pass:
    a row takes at least `n_cols` bytes. (The bound alone is nine bytes of
    address space per byte of chunk ÷ `n_cols`: tens of megabytes mapped
    and unmapped a call, which beside the pipeline's other threads costs
    more than the scan does.)

    Runs on the event loop, so it never builds the library: the copy has
    loaded it off the loop (`native_available()`, runtime/copy.py
    `parallel_table_copy`)."""
    lib = _lib
    if lib is None:
        return None
    bound = len(chunk) // max(n_cols, 1) + 1
    sample = min(len(chunk), 8192) or 1
    guess = len(chunk) * chunk.count(b"\n", 0, sample) * 5 // (4 * sample) + 64
    res = (ctypes.c_int64 * 3)()
    p = _ptr
    for max_rows in (min(guess, bound), bound):
        shape = (max_rows, max(n_cols, 0))
        offsets = np.empty(shape, dtype=np.int32)
        lengths = np.empty(shape, dtype=np.int32)
        nulls = np.empty(shape, dtype=np.bool_)
        fallback = np.empty(max_rows, dtype=np.int64)
        status = lib.etl_stage_copy_chunk(
            chunk, len(chunk), n_cols, max_rows, p(offsets), p(lengths),
            p(nulls), p(fallback), res)
        if status != _COPY_STAGE_FULL:
            break
    return (status, res[0], res[1], offsets, lengths, nulls,
            fallback[:res[2]].copy())


def int_text_fixed(arr: np.ndarray):
    """One C pass over a column of integers: (buf uint8[n, 21], lens
    int64[n]) — row r the digits of arr[r] as `str(int)` writes them,
    left-aligned and zero-padded, and how many there are. None where the
    library is not loaded or the dtype is not one of int16 / int32 /
    uint32 / int64 in native byte order: the caller then runs its numpy
    twin, which gives the same.

    Runs on the event loop (a destination renders a write before its
    request goes out), so it never builds the library: the destination
    has loaded it off the loop (`native_available()`, at `startup`)."""
    lib = _lib
    kind = _INT_TEXT_KINDS.get(arr.dtype)
    if lib is None or kind is None or arr.ndim != 1:
        return None
    vals = np.ascontiguousarray(arr)
    n = vals.shape[0]
    buf = np.empty((n, INT_TEXT_WIDTH), dtype=np.uint8)
    lens = np.empty(n, dtype=np.int64)
    lib.etl_int_text_fixed(_ptr(vals), n, kind, _ptr(buf), _ptr(lens))
    return buf, lens


def assemble_rows(n: int, pieces: list, override: "dict | None"):
    """One C pass over the rows of a columnar write's body: the pieces of
    ops/egress.py's protocol (`const`, `fixed`, `var`) copied row by row
    into one buffer, an overridden row replaced whole. Returns (out
    uint8[total], row_offsets int64[n + 1]), or None where the library is
    not loaded: the caller then runs its numpy twin, which gives the same.

    What C wants is made here, not by the callers: a `fixed` buffer that
    is a view — a column range of a wider buffer, as device egress hands
    over — is copied to contiguous rows; lengths and offsets of another
    dtype (int32 from the device or from Arrow) or layout are widened to
    contiguous int64. The output is sized exactly, from a sum per piece,
    so nothing is trimmed or copied afterwards; a table the pass refuses
    (a length over its width, offsets outside the values: the sums were
    of something else) raises ValueError, where the numpy twin would read
    a neighbour's bytes.

    Runs on the event loop, so it never builds the library: see
    `int_text_fixed`."""
    lib = _lib
    if lib is None:
        return None
    m = len(pieces)
    kinds = (ctypes.c_int32 * m)()
    data = (ctypes.c_void_p * m)()
    aux = (ctypes.c_void_p * m)()
    width = (ctypes.c_int64 * m)()
    keys = sorted(override) if override else []
    n_over = len(keys)
    if n_over and (keys[0] < 0 or keys[-1] >= n):
        raise IndexError(f"override row outside 0..{n - 1}")
    rows = np.array(keys, dtype=np.int64)
    over_data = b"".join([override[r] for r in keys])
    over_off = np.zeros(n_over + 1, dtype=np.int64)
    np.cumsum(np.fromiter((len(override[r]) for r in keys), np.int64,
                          n_over), out=over_off[1:])
    total = len(over_data)
    keep = []  # what the pointers point into, alive until the call returns
    for j, p in enumerate(pieces):
        kinds[j] = _PIECE_KINDS[p[0]]
        if p[0] == "const":
            vals = np.ascontiguousarray(p[1], dtype=np.uint8)
            width[j] = vals.size
            total += vals.size * (n - n_over)
        elif p[0] == "fixed":
            vals = p[1]
            if vals.dtype != np.uint8 or vals.ndim != 2 \
                    or vals.shape[0] < n:
                raise ValueError(f"fixed piece {j}: {vals.dtype}"
                                 f"{vals.shape} for {n} rows")
            vals = np.ascontiguousarray(vals[:n])
            lens = np.ascontiguousarray(p[2], dtype=np.int64)
            if lens.shape != (n,):
                raise ValueError(f"fixed piece {j}: {lens.shape} lengths "
                                 f"for {n} rows")
            width[j] = vals.shape[1]
            total += int(lens.sum())
            if n_over:
                total -= int(lens[rows].sum())
            aux[j] = lens.ctypes.data
            keep.append(lens)
        else:
            vals = np.ascontiguousarray(p[1], dtype=np.uint8)
            offs = np.ascontiguousarray(p[2], dtype=np.int64)
            if offs.shape != (n + 1,):
                raise ValueError(f"var piece {j}: {offs.shape} offsets "
                                 f"for {n} rows")
            width[j] = vals.size
            total += int(offs[n] - offs[0])
            if n_over:
                total -= int((offs[rows + 1] - offs[rows]).sum())
            aux[j] = offs.ctypes.data
            keep.append(offs)
        data[j] = vals.ctypes.data
        keep.append(vals)
    # a total under 0 (lengths that lie) is numpy's ValueError here
    out = np.empty(total, dtype=np.uint8)
    starts = np.empty(n + 1, dtype=np.int64)
    wrote = lib.etl_assemble_rows(
        n, m, kinds, data, aux, width, n_over, _ptr(rows), over_data,
        _ptr(over_off), _ptr(out), total, _ptr(starts))
    if wrote != total:
        raise ValueError("assemble_rows: a piece's lengths or offsets do "
                         "not describe its bytes")
    return out, starts


def pack_bmat(data, offsets, lengths, col_idx, widths, bmat, lens_out) -> bool:
    """C fast path for the device byte-matrix pack; False if unavailable."""
    lib = _load()
    if lib is None or len(col_idx) > 256:
        return False
    p = _ptr
    R, C = offsets.shape
    cols = np.ascontiguousarray(col_idx, dtype=np.int32)
    ws = np.ascontiguousarray(widths, dtype=np.int32)
    lib.etl_pack_bmat(p(data), len(data), p(offsets), p(lengths), R, C,
                      p(cols), p(ws), len(cols), p(bmat), bmat.shape[1],
                      p(lens_out))
    return True


def gather_string(data, offsets, lengths, valid, col,
                  arrow_offsets, values) -> int:
    """C fast path for Arrow string gather; -2 if unavailable."""
    lib = _load()
    if lib is None:
        return -2
    p = _ptr
    R, C = offsets.shape
    return lib.etl_gather_string(p(data), len(data), p(offsets), p(lengths),
                                 p(valid), R, C, col, p(arrow_offsets),
                                 p(values), len(values))


def pack_bmat_nibble(data, offsets, lengths, col_idx, widths, bmat,
                     lens_out, bad_rows) -> bool:
    """C nibble pack (two symbols/byte); False if unavailable."""
    lib = _load()
    if lib is None or len(col_idx) > 256:
        return False
    p = _ptr
    R, C = offsets.shape
    cols = np.ascontiguousarray(col_idx, dtype=np.int32)
    ws = np.ascontiguousarray(widths, dtype=np.int32)
    lib.etl_pack_bmat_nibble(p(data), len(data), p(offsets), p(lengths), R, C,
                             p(cols), p(ws), len(cols), p(bmat),
                             bmat.shape[1], p(lens_out), p(bad_rows))
    return True
