"""`ops/staging.stage_copy_chunk`: the C scan of a COPY chunk
(native/framer.c `etl_stage_copy_chunk`) and its numpy twin, each against
the obvious split-by-split reference of testing/fuzz.py and against each
other — every array of the StagedBatch by value, dtype and shape, padding
rows included — then end to end through the decoder under both branches.
"""

import random

import pytest

from etl_tpu.models import ColumnarBatch, Oid
from etl_tpu.models.errors import ErrorKind, EtlError
from etl_tpu.native import native_available
from etl_tpu.ops import DeviceDecoder, staging
from etl_tpu.ops.staging import ROW_BUCKETS, bucket_rows, stage_copy_chunk
from etl_tpu.postgres.codec import encode_copy_row, parse_copy_row
from etl_tpu.testing.fuzz import (check_stage_copy_chunk,
                                  stage_copy_chunk_reference)
from tests.test_ops_decode import assert_batches_equal, make_schema


@pytest.fixture(params=["native", "numpy"])
def branch(request, monkeypatch):
    """Which scan `stage_copy_chunk` runs: the C one, or the twin a
    process without the native library falls back to."""
    if request.param == "numpy":
        monkeypatch.setattr(staging, "scan_copy_chunk",
                            lambda chunk, n_cols: None)
    elif not native_available():
        pytest.skip("no C compiler: the native library did not build")
    return request.param


def rows(n, *fields):
    """`n` rows; a field is bytes, or a function of the row number."""
    return b"".join(
        b"\t".join(f(i) if callable(f) else f for f in fields) + b"\n"
        for i in range(n))


def accounts(n):
    """pgbench_accounts as COPY sends it: aid, bid, abalance, filler."""
    return rows(n, lambda i: b"%d" % (i + 1), lambda i: b"%d" % (i % 10 + 1),
                lambda i: b"%d" % ((i * 2654435761) % 2_000_000_001 - 10 ** 9),
                b" " * 84)


CASES = {
    "pgbench_accounts": (accounts(300), 4),
    "cols_1": (rows(40, lambda i: b"%d" % i), 1),
    "cols_2": (rows(40, lambda i: b"%d" % i, b"x"), 2),
    "cols_4": (rows(40, b"a", b"", lambda i: b"%d" % -i, b"zz"), 4),
    "cols_100": (rows(7, *[lambda i, c=c: b"%d" % (i * c)
                           for c in range(100)]), 100),
    "null_first": (rows(20, b"\\N", b"1", b"2"), 3),
    "null_middle": (rows(20, b"1", b"\\N", b"2"), 3),
    "null_last": (rows(20, b"1", b"2", b"\\N"), 3),
    "null_every": (rows(20, b"\\N", b"\\N", b"\\N"), 3),
    "null_some_rows": (rows(50, lambda i: b"\\N" if i % 3 else b"7",
                            lambda i: b"\\N" if i % 5 else b"NN"), 2),
    # a backslash that is not a bare \N field: the row goes to the exact
    # CPU decoder, and only that row
    "escape_backslash": (b"a\tb\n" + b"c\\\\d\te\n" + b"f\tg\n", 2),
    "escape_tab": (b"a\tb\n" * 3 + b"c\\td\te\n" + b"f\tg\n", 2),
    "escape_newline": (b"c\\nd\te\n" + b"f\tg\n" * 2, 2),
    "escape_null_inside": (b"1\tx\\N\n" + b"2\t\\Nx\n" + b"3\t\\N\n"
                           + b"4\t\\N\\N\n" + b"5\tN\n", 2),
    "escape_beside_null": (b"\\N\ta\\\\b\n" + b"\\N\tab\n", 2),
    "lone_backslash": (b"\\\t1\n" + b"2\t\\\n", 2),
    "empty_fields": (rows(10, b"", b"", b""), 3),
    "empty_lines": (b"\n" * 9, 1),
    "empty_chunk": (b"", 4),
    "no_final_newline": (rows(5, b"1", b"ab") + b"2\tcd", 2),
    "no_final_newline_null": (b"1\t\\N", 2),
    "single_row": (b"1\t2\t3\n", 3),
    "single_byte": (b"x", 1),
    "long_field": (b"1\t" + b"y" * 70_000 + b"\n" + b"2\tz\n", 2),
    "high_bytes": (rows(30, bytes(range(0x80, 0x100)), b"\xff\xfe",
                        "é\u4e2d".encode()), 3),
    # rows far shorter (or longer) than the chunk's first 8 KiB promise:
    # the C scan's guess of the row count runs out (or is generous)
    "rows_shorten": (rows(3, b"w" * 4000, b"1") + rows(5000, b"", b"\\N"), 2),
    "rows_lengthen": (rows(100, b"", b"1") + rows(30, b"w" * 4000, b"2"), 2),
    # bytes one bit from a tab, a newline and a backslash, right after
    # one: where the word-at-a-time test flags a byte falsely
    "near_misses": (b"\x08\t\x08\x0b\n" + b"]\\N\x5d\t\x0b\n"
                    + b"\\N\t\x08\n", 2),
}
for bucket in ROW_BUCKETS[:4]:
    for n in (bucket - 1, bucket, bucket + 1):
        CASES[f"bucket_{bucket}_rows_{n}"] = (
            rows(n, lambda i: b"%d" % i, b"\\N"), 2)


@pytest.mark.parametrize("case", sorted(CASES))
def test_staged_arrays_match_reference(case, branch):
    chunk, n_cols = CASES[case]
    assert not isinstance(stage_copy_chunk_reference(chunk, n_cols), str), \
        "the case is malformed: it belongs in MALFORMED"
    check_stage_copy_chunk(chunk, n_cols)


@pytest.mark.parametrize("length", range(1, 41))
def test_every_length_around_the_scan_steps(length, branch):
    """The scan steps by 16 bytes, then 8, then 1: chunks of every length
    around those, with a newline, a backslash, an N or a near miss in any
    position — well formed with one column, mostly not with two."""
    rng = random.Random(length)
    for _ in range(40):
        check_stage_copy_chunk(
            bytes(rng.choice(b"\n\n\\N0\x08\x0b]\xff")
                  for _ in range(length)), 1)
        check_stage_copy_chunk(
            bytes(rng.choice(b"\t\n\\N0") for _ in range(length)), 2)


def test_cases_cover_what_they_name(branch):
    """The cases above do reach the fallback set, the padding and the
    exact-bucket edge."""
    assert stage_copy_chunk(*CASES["escape_tab"]).cpu_fallback_rows \
        .tolist() == [3]
    assert stage_copy_chunk(*CASES["escape_null_inside"]) \
        .cpu_fallback_rows.tolist() == [0, 1, 3]
    assert stage_copy_chunk(*CASES["near_misses"]).cpu_fallback_rows \
        .tolist() == [1]
    exact = stage_copy_chunk(*CASES["bucket_1024_rows_1024"])
    assert exact.row_capacity == exact.n_rows == 1024
    over = stage_copy_chunk(*CASES["bucket_1024_rows_1025"])
    assert (over.n_rows, over.row_capacity) == (1025, bucket_rows(1025))
    assert over.nulls[1025:].all() and not over.lengths[1025:].any()
    long = stage_copy_chunk(*CASES["long_field"])
    assert long.lengths[0, 1] == 70_000 and long.max_field_len(1) == 70_000


def test_scan_says_when_its_outputs_are_full():
    """Past its binding: the C scan writes `max_rows` rows and no more,
    and says so instead of reading the rest as malformed."""
    import ctypes

    import numpy as np

    from etl_tpu import native

    if not native_available():
        pytest.skip("no C compiler: the native library did not build")
    chunk = rows(10, b"1", b"2")
    for max_rows, want in ((0, 3), (9, 3), (10, 0), (11, 0)):
        out = [np.full((max_rows + 1, 2), -7, dtype=t)
               for t in (np.int32, np.int32, np.int8)]
        res = (ctypes.c_int64 * 3)()
        assert native._lib.etl_stage_copy_chunk(
            chunk, len(chunk), 2, max_rows, *map(native._ptr, out),
            native._ptr(np.empty(max_rows + 1, np.int64)), res) == want
        assert all((a[max_rows:] == -7).all() for a in out)


COUNT = "COPY chunk: {} delimiters for {} rows × {} cols"
RAGGED = "COPY chunk: ragged rows (tab/newline mismatch)"
MALFORMED = {
    "row_short": (b"1\t2\n3\n", 2, COUNT.format(3, 2, 2)),
    "row_long": (b"1\t2\n3\t4\t5\n", 2, COUNT.format(5, 2, 2)),
    "last_row_cut": (b"1\t2\n3", 2, COUNT.format(3, 2, 2)),
    "more_columns_asked": (accounts(10), 5, COUNT.format(40, 10, 5)),
    "fewer_columns_asked": (accounts(10), 3, COUNT.format(40, 10, 3)),
    "no_columns": (b"\n", 0, COUNT.format(1, 1, 0)),
    # the counts agree and the rows do not
    "ragged_first": (b"1\t2\t3\n4\n", 2, RAGGED),
    "ragged_last": (b"1\n2\t3\t4\n", 2, RAGGED),
    "ragged_middle": (b"1\t2\n" * 20 + b"3\n4\t5\t6\n" + b"7\t8\n" * 20, 2,
                      RAGGED),
    "ragged_three_columns": (b"a\tb\tc\td\n" + b"e\tf\n" + b"g\th\ti\n", 3,
                             RAGGED),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_format_errors_are_the_same(case, branch):
    chunk, n_cols, message = MALFORMED[case]
    with pytest.raises(EtlError) as raised:
        stage_copy_chunk(chunk, n_cols)
    assert raised.value.kind is ErrorKind.COPY_FORMAT_INVALID
    assert raised.value.detail == message
    check_stage_copy_chunk(chunk, n_cols)  # and the reference agrees


def test_decode_matches_oracle(branch):
    """Staged by either branch, the chunk decodes to what the CPU COPY
    parser reads: NULLs, escapes (through the fallback rows) and a last
    row without its newline."""
    oids = [Oid.INT8, Oid.TEXT, Oid.INT4, Oid.TEXT]
    lines = []
    for i in range(300):
        lines.append(encode_copy_row([
            str(i * 1_000_003 - 150_000_000),
            None if i % 7 == 3 else
            ("tab\there\\and\nnewline" if i % 11 == 5 else f"name-{i}"),
            None if i % 5 == 0 else str(-i),
            "" if i % 13 == 0 else "\\N literal" if i % 17 == 0 else "é" * (i % 9),
        ]))
    staged = stage_copy_chunk(b"\n".join(lines), len(oids))
    assert staged.n_rows == 300
    assert staged.cpu_fallback_rows.tolist() == [
        i for i in range(300) if b"\\" in lines[i].replace(b"\t\\N", b"")]
    schema = make_schema(oids)
    dev = DeviceDecoder(schema, device_min_rows=0).decode(staged)
    cpu = ColumnarBatch.from_rows(
        schema, [parse_copy_row(line, oids) for line in lines])
    assert_batches_equal(dev, cpu)
