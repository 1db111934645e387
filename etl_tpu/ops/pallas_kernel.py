"""Pallas TPU kernel variant of the decode program — lane-packed.

The XLA path (ops/engine.build_device_program) fuses well; this kernel
exists to (a) control VMEM blocking explicitly and (b) get full VPU
lane utilization out of the byte-wise parse chain. Round-3's kernel ran
the row-major [R, L] program body and lost 18x to XLA: Mosaic padded
every 1-12-lane-wide per-column intermediate to 128 lanes, wasting >90%
of the VPU (VERDICT r3 #8). This version is the lane-packed redesign
that docstring implied:

- inputs arrive TRANSPOSED ([W, R] bytes, [C, R] lengths — XLA lays
  out the transpose once, outside the kernel);
- each field byte position is a full [R] vector (R = block rows, a
  multiple of 128), so every parse op runs on fully-populated lanes;
- the per-position work is a static Python loop over the field width
  (ops/parsers_lanes.py — semantics transcribed 1:1 from parsers.py,
  shared scalar helpers, covered by the same differential suites).

`DeviceDecoder(use_pallas=True)` selects it; no production caller does,
and its speed against the XLA program was never measured on the chip
(ROADMAP D1). A kernel Mosaic refuses to compile raises at the dispatch, like any other
compile error; only the width bound below routes a schema to the XLA
program, and it flips the decoder's `use_pallas` flag when it does.

Falls back to interpret mode off-TPU so the differential tests cover
the same code path on CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..models.pgtypes import CellKind
from .parsers_lanes import parse_column_lanes, unpack_nibbles_lanes

# Block row count. Lane-packed VMEM footprint is the [W, blk] byte block
# plus [R]-vector temporaries — far below the row-major version's
# 13.6 KB/row, so blocks can be larger; 2048 keeps the whole block +
# temporaries comfortably inside the 16 MB scoped limit even at 62
# dense columns.
DEFAULT_BLOCK_ROWS = 2048

# The fully-unrolled parse chain keeps every [blk]-row temporary of every
# byte POSITION (sum of column widths — nibble packing halves the
# gathered bytes but not the positions, so the cap is width-based) alive
# on the kernel's VMEM stack. Measured on a v5e with libtpu 0.0.34 at
# DEFAULT_BLOCK_ROWS (PR 21, one chip run): 10 x 12-byte int columns
# (120 positions) compile, nibble-packed or raw, and so does a
# 9-column mix of every non-timestamp kind at 120; 11 int columns (132)
# fail with RESOURCE_EXHAUSTED ("ran out of memory in memory space
# vmem"). An earlier libtpu took 144 and crashed the compiler at 168.
# Wide schemas take the XLA program instead: engine._dispatch_stage
# consults pallas_supported BEFORE building and flips the decoder's
# use_pallas flag, so no doomed compile attempt happens and engine
# labels stay honest.
MAX_TOTAL_WIDTH = 120


def pallas_supported(specs) -> bool:
    if jax.default_backend() != "tpu":
        return True  # interpret mode — no Mosaic, nothing to crash
    return sum(w for _, _, w, _ in specs) <= MAX_TOTAL_WIDTH


def build_pallas_program(specs: tuple[tuple[int, CellKind, int, int], ...],
                         nibble: bool = False,
                         block_rows: int = DEFAULT_BLOCK_ROWS,
                         interpret: bool | None = None,
                         pred=None):
    """Same contract as engine.build_device_program, lowered via Pallas.

    With `pred` (predicate.CompiledRowFilter) the kernel is the FUSED
    coerce→filter→pack step: the publication row filter evaluates inside
    the kernel body over the parsed [R]-lane component vectors (the same
    `predicate.device_keep` evaluator the XLA twin uses — comps dicts
    have the identical shape in both conventions), the keep bits mask the
    packed words in-register so filtered rows' values never reach the HBM
    output block, and a second (1, blk) output carries the keep bits out.
    The row-compaction epilogue (`bitpack.compact_packed` — an in-block
    exclusive prefix-sum scatter) runs as XLA ops over the kernel's
    outputs: cross-block survivor destinations depend on every earlier
    block's count, which a grid-parallel kernel cannot know, so the
    scatter lives outside the grid while the per-row verdicts stay fused
    in-kernel. Output structure matches the XLA twin exactly:
    (words_compacted, keep_mask, counts)."""
    from .bitpack import compact_packed, layout_for_specs, pack_device

    layout = layout_for_specs(specs)
    k_out = layout.n_words
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    total_w = sum(w for _, _, w, _ in specs)
    w_in = total_w // 2 if nibble else total_w
    ref_cols = frozenset(pred.referenced_indices) if pred is not None \
        else frozenset()

    def parse_block(bmat_ref, len_ref):
        columns = []
        colmap = {}
        w_off = 0
        for j, (col_idx, kind, width, _bw) in enumerate(specs):
            if nibble:
                packed = [bmat_ref[w_off // 2 + i, :].astype(jnp.int32)
                          for i in range(width // 2)]
                rows = unpack_nibbles_lanes(packed, width)
            else:
                rows = [bmat_ref[w_off + i, :].astype(jnp.int32)
                        for i in range(width)]
            w_off += width
            lengths = len_ref[j, :].astype(jnp.int32)
            comp, ok = parse_column_lanes(kind, rows, lengths)
            columns.append((ok, comp))
            if col_idx in ref_cols:
                colmap[col_idx] = (comp, ok, lengths == 0)
        return columns, colmap

    def kernel(bmat_ref, len_ref, out_ref):
        columns, _ = parse_block(bmat_ref, len_ref)
        out_ref[:, :] = pack_device(layout, columns)

    def kernel_filtered(bmat_ref, len_ref, flags_ref, out_ref, keep_ref):
        columns, colmap = parse_block(bmat_ref, len_ref)
        keep = pred.device_keep(colmap, flags_ref[0, :].astype(jnp.int32))
        keep_i = keep.astype(jnp.int32)
        # mask in-register: a filtered row's packed words never reach the
        # HBM output block — the epilogue scatter only moves survivors
        out_ref[:, :] = pack_device(layout, columns) \
            * keep_i[None, :].astype(jnp.uint32)
        keep_ref[:, :] = keep_i[None, :]

    def fn(bmat, lengths, row_flags=None):
        R = bmat.shape[0]
        blk = min(block_rows, R)
        assert R % blk == 0, (R, blk)
        grid = (R // blk,)
        # transpose OUTSIDE the kernel: one XLA layout pass, then every
        # kernel read of a byte position is a contiguous [blk] vector
        bmat_t = bmat.T
        lengths_t = lengths.T
        if pred is None:
            return pl.pallas_call(
                kernel,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((w_in, blk), lambda i: (0, i)),
                    pl.BlockSpec((lengths.shape[1], blk), lambda i: (0, i)),
                ],
                out_specs=pl.BlockSpec((k_out, blk), lambda i: (0, i)),
                out_shape=jax.ShapeDtypeStruct((k_out, R), jnp.uint32),
                interpret=interpret,
            )(bmat_t, lengths_t)
        words, keep = pl.pallas_call(
            kernel_filtered,
            grid=grid,
            in_specs=[
                pl.BlockSpec((w_in, blk), lambda i: (0, i)),
                pl.BlockSpec((lengths.shape[1], blk), lambda i: (0, i)),
                pl.BlockSpec((1, blk), lambda i: (0, i)),
            ],
            out_specs=[
                pl.BlockSpec((k_out, blk), lambda i: (0, i)),
                pl.BlockSpec((1, blk), lambda i: (0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((k_out, R), jnp.uint32),
                jax.ShapeDtypeStruct((1, R), jnp.int32),
            ],
            interpret=interpret,
        )(bmat_t, lengths_t, row_flags.reshape(1, R))
        # compaction epilogue: in-block prefix-sum scatter of survivors
        with jax.named_scope("compact"):
            return compact_packed(words, keep[0] > 0, 1)

    # the device trace's module line reads jit_etl_decode_pallas
    fn.__name__ = fn.__qualname__ = "etl_decode_pallas"
    return fn
