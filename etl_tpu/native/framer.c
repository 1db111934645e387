/* pgoutput message framer — the native host hot path.
 *
 * Walks a batch of logical-replication message payloads (concatenated in one
 * buffer) and emits, for every Insert/Update/Delete, the absolute
 * offset/length/flag of each tuple field — zero-copy: field bytes are never
 * moved, the offsets point straight into the WAL payload buffer that is then
 * uploaded to the device whole.
 *
 * This replaces the per-tuple decode loop of the reference
 * (crates/etl/src/postgres/codec/event.rs) with an index-building pass;
 * the actual parsing happens on the TPU (etl_tpu/ops). Python fallback:
 * etl_tpu/native/__init__.py.
 *
 * Build: cc -O3 -shared -fPIC framer.c -o _framer.so  (see native/__init__.py)
 */

#include <stdint.h>
#include <string.h>
#if defined(__SSE2__)
#include <emmintrin.h>
#endif

#define FLAG_VALUE 0
#define FLAG_NULL 1
#define FLAG_TOAST 2
#define FLAG_BINARY 3

static inline uint32_t be32(const uint8_t *p) {
    return ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16) |
           ((uint32_t)p[2] << 8) | (uint32_t)p[3];
}

static inline uint16_t be16(const uint8_t *p) {
    return ((uint16_t)p[0] << 8) | (uint16_t)p[1];
}

/* Walk one TupleData at buf[pos..end); fill n_cols entries of off/len/flag.
 * Returns new pos, or -1 on malformed input. */
static int64_t walk_tuple(const uint8_t *buf, int64_t pos, int64_t end,
                          int32_t n_cols, int64_t base,
                          int32_t *off, int32_t *len, uint8_t *flag) {
    if (pos + 2 > end) return -1;
    int32_t ncols = (int32_t)be16(buf + pos);
    pos += 2;
    if (ncols != n_cols) return -1;
    for (int32_t c = 0; c < ncols; c++) {
        if (pos + 1 > end) return -1;
        uint8_t kind = buf[pos++];
        switch (kind) {
        case 'n':
            off[c] = 0; len[c] = 0; flag[c] = FLAG_NULL;
            break;
        case 'u':
            off[c] = 0; len[c] = 0; flag[c] = FLAG_TOAST;
            break;
        case 't':
        case 'b': {
            if (pos + 4 > end) return -1;
            int32_t vlen = (int32_t)be32(buf + pos);
            pos += 4;
            if (vlen < 0 || pos + vlen > end) return -1;
            off[c] = (int32_t)(pos - base);
            len[c] = vlen;
            flag[c] = kind == 't' ? FLAG_VALUE : FLAG_BINARY;
            pos += vlen;
            break;
        }
        default:
            return -1;
        }
    }
    return pos;
}

/* Frame a batch of pgoutput messages.
 *
 * Outputs (per message i):
 *   kind_out[i]   message tag byte ('I','U','D','B','C','R','T','M','O','Y'),
 *                 0 if malformed
 *   relid_out[i]  relation oid for I/U/D, else 0
 *   old_kind[i]   0 none, 'K' key tuple, 'O' full old tuple (U/D)
 *   new_/old_ arrays: [i*n_cols + c] field offset (relative to buf start),
 *                 length, flag. For D the old tuple fills the old_ arrays.
 *
 * Returns -1 if every message framed cleanly, else the index of the first
 * malformed message (framing stops there).
 */
int64_t etl_frame_pgoutput(const uint8_t *buf, int64_t buf_len,
                           const int64_t *msg_off, const int32_t *msg_len,
                           int64_t n_msgs, int32_t n_cols,
                           uint8_t *kind_out, int32_t *relid_out,
                           uint8_t *old_kind,
                           int32_t *new_off, int32_t *new_len,
                           uint8_t *new_flag, int32_t *old_off,
                           int32_t *old_len, uint8_t *old_flag) {
    for (int64_t i = 0; i < n_msgs; i++) {
        int64_t pos = msg_off[i];
        int64_t end = pos + msg_len[i];
        if (end > buf_len || msg_len[i] < 1) return i;
        uint8_t tag = buf[pos];
        kind_out[i] = tag;
        relid_out[i] = 0;
        old_kind[i] = 0;
        int32_t *noff = new_off + i * n_cols;
        int32_t *nlen = new_len + i * n_cols;
        uint8_t *nflag = new_flag + i * n_cols;
        int32_t *ooff = old_off + i * n_cols;
        int32_t *olen = old_len + i * n_cols;
        uint8_t *oflag = old_flag + i * n_cols;
        for (int32_t c = 0; c < n_cols; c++) {
            nflag[c] = FLAG_NULL; noff[c] = 0; nlen[c] = 0;
            oflag[c] = FLAG_NULL; ooff[c] = 0; olen[c] = 0;
        }
        switch (tag) {
        case 'I': {
            if (pos + 6 > end) { kind_out[i] = 0; return i; }
            relid_out[i] = (int32_t)be32(buf + pos + 1);
            if (buf[pos + 5] != 'N') { kind_out[i] = 0; return i; }
            pos = walk_tuple(buf, pos + 6, end, n_cols, 0, noff, nlen, nflag);
            if (pos < 0) { kind_out[i] = 0; return i; }
            break;
        }
        case 'U': {
            if (pos + 6 > end) { kind_out[i] = 0; return i; }
            relid_out[i] = (int32_t)be32(buf + pos + 1);
            pos += 5;
            uint8_t marker = buf[pos];
            if (marker == 'O' || marker == 'K') {
                old_kind[i] = marker;
                pos = walk_tuple(buf, pos + 1, end, n_cols, 0, ooff, olen,
                                 oflag);
                if (pos < 0 || pos + 1 > end) { kind_out[i] = 0; return i; }
                marker = buf[pos];
            }
            if (marker != 'N') { kind_out[i] = 0; return i; }
            pos = walk_tuple(buf, pos + 1, end, n_cols, 0, noff, nlen, nflag);
            if (pos < 0) { kind_out[i] = 0; return i; }
            break;
        }
        case 'D': {
            if (pos + 6 > end) { kind_out[i] = 0; return i; }
            relid_out[i] = (int32_t)be32(buf + pos + 1);
            uint8_t marker = buf[pos + 5];
            if (marker != 'O' && marker != 'K') { kind_out[i] = 0; return i; }
            old_kind[i] = marker;
            pos = walk_tuple(buf, pos + 6, end, n_cols, 0, ooff, olen, oflag);
            if (pos < 0) { kind_out[i] = 0; return i; }
            break;
        }
        default:
            /* non-row message: host decodes it (rare) */
            break;
        }
    }
    return -1;
}

/* Pack dense-column field bytes into the device byte matrix.
 *
 * bmat[r, w_off(c)..w_off(c)+min(len, width)) = field bytes, zero elsewhere;
 * lens_out[r*n_dense + j] = min(len, 255, width). The engine uploads bmat +
 * lens; this replaces a per-column numpy gather (one pass, cache-friendly).
 * bmat must be zeroed by the caller (numpy zeros) or dirty regions beyond
 * lens are never read by the device program anyway — we still zero pad up
 * to width for deterministic device inputs. */
void etl_pack_bmat(const uint8_t *data, int64_t data_len,
                   const int32_t *offsets, const int32_t *lengths,
                   int64_t n_rows, int32_t n_cols, const int32_t *col_idx,
                   const int32_t *widths, int32_t n_dense, uint8_t *bmat,
                   int32_t total_w, uint8_t *lens_out) {
    /* per-column output offsets — defensive against caller mismatch: a C
     * entry point fed from a dynamic language must never write past the
     * bmat row stride even if widths[] disagrees with total_w (found by
     * scripts/sanitize_framer.py's adversarial hammer) */
    int32_t w_off[256];
    int32_t acc = 0;
    if (n_dense > 256) n_dense = 256;
    for (int32_t j = 0; j < n_dense; j++) {
        w_off[j] = acc;
        acc += widths[j] > 0 ? widths[j] : 0;
    }
    for (int64_t r = 0; r < n_rows; r++) {
        const int32_t *row_off = offsets + r * n_cols;
        const int32_t *row_len = lengths + r * n_cols;
        uint8_t *out_row = bmat + r * total_w;
        for (int32_t j = 0; j < n_dense; j++) {
            int32_t c = col_idx[j];
            int32_t w = widths[j];
            if (w < 0) w = 0;
            if (w_off[j] >= total_w) {
                /* clamp fired: zero the length so the numpy-empty
                 * lens buffer never leaks uninitialized bytes to the
                 * device decode path */
                lens_out[r * n_dense + j] = 0;
                continue;
            }
            if (w > total_w - w_off[j]) w = total_w - w_off[j];
            int32_t len = row_len[c];
            if (len < 0) len = 0;
            if (len > w) len = w;
            int64_t off = row_off[c];
            if (off < 0 || off + len > data_len) len = 0;
            uint8_t *dst = out_row + w_off[j];
            const uint8_t *src = data + off;
            for (int32_t k = 0; k < len; k++) dst[k] = src[k];
            for (int32_t k = len; k < w; k++) dst[k] = 0;
            lens_out[r * n_dense + j] = (uint8_t)(len > 255 ? 255 : len);
        }
    }
}

/* Gather k string columns into Arrow layout, one call for all of them:
 * column j (cols[j] of the n_cols) takes lens[j * n_rows + r] bytes of row r
 * (0 for a NULL, an unchanged TOAST or a masked row), its values follow
 * column j-1's in `values`, and its int32 offsets arrow_offsets[j *
 * (n_rows + 1) ...] start again at 0. Returns total bytes written, or -1
 * if they would exceed cap or a column lies outside n_cols. */
int64_t etl_gather_strings(const uint8_t *data, int64_t data_len,
                           const int32_t *offsets, int64_t n_rows,
                           int32_t n_cols, const int32_t *cols, int32_t k,
                           const int32_t *lens, int32_t *arrow_offsets,
                           uint8_t *values, int64_t cap) {
    int64_t pos = 0;
    for (int32_t j = 0; j < k; j++) {
        int32_t col = cols[j];
        if (col < 0 || col >= n_cols) return -1;
        int32_t *ao = arrow_offsets + j * (n_rows + 1);
        int64_t base = pos;
        ao[0] = 0;
        for (int64_t r = 0; r < n_rows; r++) {
            int32_t len = lens[j * n_rows + r];
            int64_t off = offsets[r * n_cols + col];
            if (len < 0 || off < 0 || off + len > data_len) len = 0;
            if (pos + len > cap) return -1;
            if (len) memcpy(values + pos, data + off, (size_t)len);
            pos += len;
            ao[r + 1] = (int32_t)(pos - base);
        }
    }
    return pos;
}

/* Nibble-packed variant of etl_pack_bmat: two symbols per byte.
 *
 * Symbol alphabet (4 bits): 0-9 = digits, 10 '-', 11 '+', 12 '.', 13 ':',
 * 14 ' ', 15 = pad. Covers int/float(fixed)/date/time/timestamp text;
 * any other byte (e.g. 'e' exponents, NaN/Infinity) marks the row in
 * bad_rows for the CPU oracle. Halves the host→device transfer.
 * widths[] must all be even; bmat has sum(widths)/2 bytes per row. */
void etl_pack_bmat_nibble(const uint8_t *data, int64_t data_len,
                          const int32_t *offsets, const int32_t *lengths,
                          int64_t n_rows, int32_t n_cols,
                          const int32_t *col_idx, const int32_t *widths,
                          int32_t n_dense, uint8_t *bmat, int32_t packed_w,
                          uint8_t *lens_out, uint8_t *bad_rows) {
    static uint8_t code_of[256];
    static int init = 0;
    if (!init) {
        for (int i = 0; i < 256; i++) code_of[i] = 0xFF;
        for (int d = 0; d < 10; d++) code_of['0' + d] = (uint8_t)d;
        code_of['-'] = 10; code_of['+'] = 11; code_of['.'] = 12;
        code_of[':'] = 13; code_of[' '] = 14;
        init = 1;
    }
    int32_t w_off[256];
    int32_t acc = 0;
    if (n_dense > 256) n_dense = 256;
    for (int32_t j = 0; j < n_dense; j++) {
        w_off[j] = acc;
        acc += widths[j] > 0 ? widths[j] / 2 : 0;
    }
    for (int64_t r = 0; r < n_rows; r++) {
        const int32_t *row_off = offsets + r * n_cols;
        const int32_t *row_len = lengths + r * n_cols;
        uint8_t *out_row = bmat + r * packed_w;
        uint8_t bad = 0;
        for (int32_t j = 0; j < n_dense; j++) {
            int32_t c = col_idx[j];
            int32_t w = widths[j];
            if (w < 0) w = 0;
            /* same caller-mismatch defense as etl_pack_bmat, in packed
             * (w/2) units */
            if (w_off[j] >= packed_w) {
                lens_out[r * n_dense + j] = 0;
                continue;
            }
            if (w / 2 > packed_w - w_off[j]) w = (packed_w - w_off[j]) * 2;
            int32_t len = row_len[c];
            if (len < 0) len = 0;
            if (len > w) len = w;
            int64_t off = row_off[c];
            if (off < 0 || off + len > data_len) len = 0;
            const uint8_t *src = data + off;
            uint8_t *dst = out_row + w_off[j];
            /* PLANAR layout: byte k holds symbol k in the high nibble and
             * symbol k + w/2 in the low nibble — the device reassembles
             * with a lane concatenation (interleave reshapes don't lower
             * under Mosaic). */
            int32_t half = w / 2;
            for (int32_t k = 0; k < half; k++) {
                uint8_t a = 0x0F, b = 0x0F;
                if (k < len) {
                    a = code_of[src[k]];
                    bad |= (uint8_t)(a >> 7);
                }
                int32_t k2 = k + half;
                if (k2 < len) {
                    b = code_of[src[k2]];
                    bad |= (uint8_t)(b >> 7);
                }
                dst[k] = (uint8_t)((a << 4) | (b & 0x0F));
            }
            lens_out[r * n_dense + j] = (uint8_t)(len > 255 ? 255 : len);
        }
        bad_rows[r] = bad ? 1 : 0;
    }
}

/* Scan a block of backend messages for the run of CopyData ('d') messages
 * at its head (postgres/wire.py `copy_out`: a server sends one CopyData
 * per row, and the socket hands them over a few thousand at a time).
 *
 * Each 'd' message is tag, int32 length (counts itself, not the tag),
 * payload. The payloads of the run are written to `out` back to back, the
 * 5-byte headers dropped; `out` is the caller's, buf_len bytes, apart from
 * `buf`. The scan stops at the first message it may not take:
 *
 *   COPY_SCAN_MORE  the block ends there, or inside that 'd' message
 *                   (header or payload cut): the caller brings more bytes
 *   COPY_SCAN_SLOW  another tag, or a 'd' whose length is under 4 or over
 *                   PG's 1 GB message cap (the bound `_read_message`
 *                   keeps): the caller's per-message logic decides
 *
 * res[0] = bytes consumed, which is the offset of that first message;
 * res[1] = payload bytes written; res[2] = messages taken. Untrusted
 * input: no read past buf_len, no write past res[0] - 5 * res[2] bytes.
 * Python twin: native/__init__.py `_scan_copy_data_py`. */
#define COPY_SCAN_MORE 0
#define COPY_SCAN_SLOW 1
#define COPY_MAX_PAYLOAD ((int64_t)1 << 30)

int32_t etl_scan_copy_data(const uint8_t *buf, int64_t buf_len,
                           uint8_t *out, int64_t *res) {
    int64_t pos = 0, written = 0, taken = 0;
    int32_t stop = COPY_SCAN_MORE;
    while (pos < buf_len) {
        if (buf[pos] != 'd') { stop = COPY_SCAN_SLOW; break; }
        if (pos + 5 > buf_len) break;
        int64_t payload = (int64_t)(int32_t)be32(buf + pos + 1) - 4;
        if (payload < 0 || payload > COPY_MAX_PAYLOAD) {
            stop = COPY_SCAN_SLOW;
            break;
        }
        if (pos + 5 + payload > buf_len) break;
        memcpy(out + written, buf + pos + 5, (size_t)payload);
        written += payload;
        pos += 5 + payload;
        taken++;
    }
    res[0] = pos;
    res[1] = written;
    res[2] = taken;
    return stop;
}

/* Stage a chunk of COPY text rows in one pass over its bytes
 * (ops/staging.py `stage_copy_chunk`; runs once per chunk of the initial
 * copy, 62,500 rows of 6.5 MB in a pgbench copy).
 *
 * `buf` holds whole rows, each `n_cols` fields parted by tabs and closed by
 * a newline (the caller appends the last newline where the stream left it
 * out). For row r, column c the scan writes at [r * n_cols + c]:
 *
 *   offsets  where the field starts in buf
 *   lengths  its length in bytes, 0 where it is NULL
 *   nulls    1 where the field is exactly "\N"
 *
 * and appends r to `fallback` (ascending) where the row holds a backslash
 * that is not such a field: an escape, the exact CPU decoder's to read.
 * The outputs are the caller's, `max_rows` rows each. Well-formed rows
 * take at least n_cols bytes each, so buf_len / n_cols + 1 rows always
 * do; a caller that guesses fewer is told when the guess runs out.
 *
 * res[0] = newlines in buf (the rows), res[1] = tabs and newlines,
 * res[2] = rows appended to `fallback`. Malformed, the first two count
 * the whole of buf, which is what the caller's error message quotes.
 *
 *   COPY_STAGE_OK      res[1] == res[0] * n_cols and every row well formed
 *   COPY_STAGE_COUNT   res[1] != res[0] * n_cols
 *   COPY_STAGE_RAGGED  the counts agree and some row has another number of
 *                      tabs than n_cols - 1 (or bytes follow the last
 *                      newline)
 *   COPY_STAGE_FULL    row `max_rows` began: nothing in res, scan again
 *                      with more room
 *
 * Untrusted input: no read past buf_len, no write past max_rows rows.
 * Numpy twin: ops/staging.py `_scan_copy_chunk_np`. */
#define COPY_STAGE_OK 0
#define COPY_STAGE_COUNT 1
#define COPY_STAGE_RAGGED 2
#define COPY_STAGE_FULL 3

#define SWAR_ONES 0x0101010101010101ULL
#define SWAR_HIGHS 0x8080808080808080ULL

/* 0x80 in the lowest byte of `w` that equals `c`, and in no byte below it
 * (bytes above a match may be flagged falsely: the caller looks at the
 * byte itself). The scan steps by 16 bytes where SSE2 compares them at
 * once, and takes the last 8..15 bytes of every chunk, and all of them
 * on another machine, through this. */
static inline uint64_t swar_has(uint64_t w, uint8_t c) {
    uint64_t x = w ^ (SWAR_ONES * c);
    return (x - SWAR_ONES) & ~x;
}

int32_t etl_stage_copy_chunk(const uint8_t *buf, int64_t buf_len,
                             int32_t n_cols, int64_t max_rows,
                             int32_t *offsets, int32_t *lengths,
                             uint8_t *nulls, int64_t *fallback,
                             int64_t *res) {
    int64_t row = 0, n_delims = 0, n_fallback = 0;
    int64_t field_start = 0, pos = 0;
    int64_t row_backslashes = 0, row_nulls = 0;
    int32_t col = 0, last_col = n_cols - 1;
    int ragged = n_cols < 1;

    while (!ragged && pos < buf_len) {
        /* one bit (from 16 bytes) or one 0x80 (from 8) for each byte of
         * the step that may be a tab, a newline or a backslash */
        uint64_t hits;
        int64_t step;
        int shift = 0;
#if defined(__SSE2__)
        if (pos + 16 <= buf_len) {
            __m128i v = _mm_loadu_si128((const __m128i *)(buf + pos));
            __m128i m = _mm_or_si128(
                _mm_or_si128(_mm_cmpeq_epi8(v, _mm_set1_epi8('\t')),
                             _mm_cmpeq_epi8(v, _mm_set1_epi8('\n'))),
                _mm_cmpeq_epi8(v, _mm_set1_epi8('\\')));
            hits = (uint32_t)_mm_movemask_epi8(m);
            step = 16;
        } else
#endif
        if (pos + 8 <= buf_len) {
            uint64_t w;
            memcpy(&w, buf + pos, 8);
#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ == __ORDER_BIG_ENDIAN__
            w = __builtin_bswap64(w);
#endif
            hits = (swar_has(w, '\t') | swar_has(w, '\n') |
                    swar_has(w, '\\')) & SWAR_HIGHS;
            step = 8;
            shift = 3;
        } else {
            hits = 1;  /* the last bytes, one at a time */
            step = 1;
        }
        for (; hits; hits &= hits - 1) {
            int64_t at = pos + (__builtin_ctzll(hits) >> shift);
            uint8_t c = buf[at];
            if (c == '\\') { row_backslashes++; continue; }
            if (c != '\t' && c != '\n') continue;
            if (row >= max_rows) return COPY_STAGE_FULL;
            /* a tab in the last column, or a newline before it */
            if ((c == '\t') == (col == last_col)) {
                pos = at;  /* the tail loop counts from this byte */
                step = 0;
                ragged = 1;
                break;
            }
            n_delims++;
            int64_t len = at - field_start;
            int is_null = len == 2 && buf[field_start] == '\\' &&
                          buf[field_start + 1] == 'N';
            int64_t cell = row * n_cols + col;
            offsets[cell] = (int32_t)field_start;
            lengths[cell] = is_null ? 0 : (int32_t)len;
            nulls[cell] = (uint8_t)is_null;
            row_nulls += is_null;
            field_start = at + 1;
            if (c == '\t') { col++; continue; }
            if (row_backslashes != row_nulls) fallback[n_fallback++] = row;
            row++;
            col = 0;
            row_backslashes = row_nulls = 0;
        }
        pos += step;
    }
    int64_t n_rows = row;
    if (!ragged && field_start != buf_len) ragged = 1;  /* no last newline */
    /* malformed: the error quotes the counts of the whole chunk */
    for (; pos < buf_len; pos++) {
        n_delims += (buf[pos] == '\t') | (buf[pos] == '\n');
        n_rows += buf[pos] == '\n';
    }
    res[0] = n_rows;
    res[1] = n_delims;
    res[2] = n_fallback;
    if (n_delims != n_rows * (int64_t)n_cols) return COPY_STAGE_COUNT;
    return ragged ? COPY_STAGE_RAGGED : COPY_STAGE_OK;
}

/* Render a column of integers as decimal text, one value a row
 * (ops/egress.py `int_text_fixed`): row r of `buf` (n rows of
 * INT_TEXT_WIDTH bytes, the caller's) gets the digits of vals[r] as
 * `str(int)` writes them — a '-' first where it is negative, no leading
 * zeros, "0" for zero — left-aligned and zero-padded, and lens[r] how
 * many bytes that is. 21 bytes hold every int64 (INT64_MIN is 20) and
 * are the width the numpy twin's `astype("U21")` gives.
 *
 * `kind` says what `vals` points at; another value writes nothing and
 * returns -1. Numpy twin: ops/egress.py `_int_text_fixed_np`. */
#define INT_TEXT_WIDTH 21
#define INT_TEXT_I16 0
#define INT_TEXT_I32 1
#define INT_TEXT_U32 2
#define INT_TEXT_I64 3

int32_t etl_int_text_fixed(const void *vals, int64_t n, int32_t kind,
                           uint8_t *buf, int64_t *lens) {
    if (kind < INT_TEXT_I16 || kind > INT_TEXT_I64) return -1;
    for (int64_t r = 0; r < n; r++) {
        int64_t v;
        switch (kind) {
        case INT_TEXT_I16: v = ((const int16_t *)vals)[r]; break;
        case INT_TEXT_I32: v = ((const int32_t *)vals)[r]; break;
        case INT_TEXT_U32: v = ((const uint32_t *)vals)[r]; break;
        default: v = ((const int64_t *)vals)[r]; break;
        }
        /* the magnitude in unsigned arithmetic: -INT64_MIN does not fit */
        uint64_t mag = v < 0 ? (uint64_t)0 - (uint64_t)v : (uint64_t)v;
        uint8_t digits[20];
        int32_t nd = 0;
        do {
            digits[nd++] = (uint8_t)('0' + mag % 10);
            mag /= 10;
        } while (mag);
        uint8_t *row = buf + r * INT_TEXT_WIDTH;
        int32_t len = 0;
        if (v < 0) row[len++] = '-';
        while (nd) row[len++] = digits[--nd];
        lens[r] = len;
        memset(row + len, 0, (size_t)(INT_TEXT_WIDTH - len));
    }
    return 0;
}

/* Assemble the body of a columnar write — one line a row — in one pass
 * over the rows (ops/egress.py `assemble_rows`; a ClickHouse TSV insert
 * is 12 pieces a row, a Snowflake NDJSON line some 4 a column).
 *
 * A row is the bytes of `n_pieces` pieces, in order. Piece j is
 *
 *   PIECE_CONST  data[j]: width[j] bytes, the same for every row
 *   PIECE_FIXED  data[j]: n rows of width[j] bytes, left-aligned, of
 *                which row r gives its first aux[j][r]
 *   PIECE_VAR    data[j]: width[j] bytes, of which row r gives
 *                [aux[j][r], aux[j][r + 1])  (aux[j]: n + 1 offsets)
 *
 * An overridden row — `over_rows`, ascending, `n_over` of them — takes
 * no byte of any piece and instead over_data[over_off[i], over_off[i+1])
 * verbatim. The bytes go to `out` back to back and row r starts at
 * row_offsets[r]; row_offsets[n] is the total, which is also returned.
 *
 * The caller sizes `out` exactly, from the pieces' lengths. A length
 * under 0 or over its piece's width, offsets that fall or leave the
 * values, override rows out of order or range, or a byte past `out_cap`
 * stop the pass and return -1: no read outside a piece as described, no
 * write past out_cap bytes or n + 1 offsets. Numpy twin: ops/egress.py
 * `_assemble_rows_np`. */
#define PIECE_CONST 0
#define PIECE_FIXED 1
#define PIECE_VAR 2

int64_t etl_assemble_rows(int64_t n, int32_t n_pieces, const int32_t *kind,
                          const uint8_t *const *data,
                          const int64_t *const *aux, const int64_t *width,
                          int64_t n_over, const int64_t *over_rows,
                          const uint8_t *over_data, const int64_t *over_off,
                          uint8_t *out, int64_t out_cap,
                          int64_t *row_offsets) {
    int64_t pos = 0, oi = 0;
    for (int32_t j = 0; j < n_pieces; j++) {
        if (kind[j] < PIECE_CONST || kind[j] > PIECE_VAR || width[j] < 0)
            return -1;
    }
    for (int64_t r = 0; r < n; r++) {
        row_offsets[r] = pos;
        if (oi < n_over && over_rows[oi] <= r) {
            if (over_rows[oi] != r) return -1;  /* below r: not ascending */
            int64_t a = over_off[oi], len = over_off[oi + 1] - a;
            if (a < 0 || len < 0 || len > out_cap - pos) return -1;
            if (len) memcpy(out + pos, over_data + a, (size_t)len);
            pos += len;
            oi++;
            continue;
        }
        for (int32_t j = 0; j < n_pieces; j++) {
            const uint8_t *src;
            int64_t len;
            switch (kind[j]) {
            case PIECE_CONST:
                src = data[j];
                len = width[j];
                break;
            case PIECE_FIXED:
                src = data[j] + r * width[j];
                len = aux[j][r];
                if (len < 0 || len > width[j]) return -1;
                break;
            default: {
                int64_t a = aux[j][r];
                len = aux[j][r + 1] - a;
                if (a < 0 || len < 0 || a + len > width[j]) return -1;
                src = data[j] + a;
                break;
            }
            }
            if (len > out_cap - pos) return -1;
            if (len == 1) out[pos] = src[0];  /* the separators */
            else if (len) memcpy(out + pos, src, (size_t)len);
            pos += len;
        }
    }
    if (oi != n_over) return -1;  /* a row at or past n */
    row_offsets[n] = pos;
    return pos;
}
