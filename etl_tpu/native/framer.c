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

/* Gather one string column into Arrow layout: contiguous values + int32
 * offsets[n_rows+1]. valid[r]==0 rows contribute zero bytes. Returns total
 * bytes written, or -1 if it would exceed cap. */
int64_t etl_gather_string(const uint8_t *data, int64_t data_len,
                          const int32_t *offsets, const int32_t *lengths,
                          const uint8_t *valid, int64_t n_rows,
                          int32_t n_cols, int32_t col,
                          int32_t *arrow_offsets, uint8_t *values,
                          int64_t cap) {
    int64_t pos = 0;
    arrow_offsets[0] = 0;
    for (int64_t r = 0; r < n_rows; r++) {
        if (valid[r]) {
            int32_t len = lengths[r * n_cols + col];
            int64_t off = offsets[r * n_cols + col];
            if (len < 0 || off < 0 || off + len > data_len) len = 0;
            if (pos + len > cap) return -1;
            const uint8_t *src = data + off;
            uint8_t *dst = values + pos;
            for (int32_t k = 0; k < len; k++) dst[k] = src[k];
            pos += len;
        }
        arrow_offsets[r + 1] = (int32_t)pos;
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
