"""The one comparison of two decoded batches.

`batches_identical` is what "pipelined == serial", "mesh == single
device", "fused filter == host oracle" and chip_smoke's engine checks
all mean by equal: same rows, same survivor mapping, same validity, and
the same BYTES in every valid cell (so -0.0 != 0.0 and a NaN equals only
the same NaN)."""

from __future__ import annotations

import numpy as np


def batches_identical(a, b) -> bool:
    """Byte identity of two ColumnarBatches, survivor mapping
    (`source_rows`) included — a filter that kept the right count but
    the wrong rows differs here. Cells under a cleared validity bit are
    not compared."""
    if a.num_rows != b.num_rows:
        return False
    sa, sb = a.source_rows, b.source_rows
    if (sa is None) != (sb is None) \
            or (sa is not None and not np.array_equal(sa, sb)):
        return False
    for ca, cb in zip(a.columns, b.columns):
        va, vb = np.asarray(ca.validity), np.asarray(cb.validity)
        if not np.array_equal(va, vb) or ca.is_dense != cb.is_dense:
            return False
        if ca.is_dense:
            da, db = np.where(va, ca.data, 0), np.where(vb, cb.data, 0)
            if da.dtype != db.dtype or da.tobytes() != db.tobytes():
                return False
        elif any(ca.value(i) != cb.value(i) for i in np.flatnonzero(va)):
            return False
    return True
