#!/usr/bin/env python3
"""The control and the planted faults: the rest of a run, with the timed
path broken underneath, has to come out `correct: false`.

    python3 benchmark/tests/control.py --fault ack_and_drop --workload <cell> \\
        --seed <n> --seconds <s> [--rehearse]

The system runs no model and states no precision, so the control breaks the
one guarantee the configurations state, at-least-once delivery:

  ack_and_drop   the destination acknowledges every write and drops each
                 fifth one (the control)
  half_batch     the sink keeps the first half of every batch and leaves the
                 rest out (null-sink cells)
  altered_value  one decoded value per batch is altered where it is
                 produced (`DeviceDecoder._complete`)

On the chip it is run at the cell's own size on three seeds or more
(PERF.md); `test_control.py` keeps it at rehearsal size.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as harness  # noqa: E402

FAULTS = ("ack_and_drop", "half_batch", "altered_value")


def plant(fault: str) -> None:
    if fault == "ack_and_drop":
        from etl_tpu.destinations.base import WriteAck

        inner_make = harness.Run.make_pipeline

        def make_pipeline(self, port, destination, pipeline_id=1):
            calls = {"n": 0}
            for attr in ("write_event_batches", "write_table_batch"):
                real = getattr(destination, attr)

                async def lossy(*args, _real=real, **kw):
                    calls["n"] += 1
                    if calls["n"] % 5 == 0:
                        return WriteAck.durable()
                    return await _real(*args, **kw)

                setattr(destination, attr, lossy)
            return inner_make(self, port, destination, pipeline_id)

        harness.Run.make_pipeline = make_pipeline
    elif fault == "half_batch":
        cls = type(harness.make_null_destination())
        keep = cls._keep

        def half(self, batch, commit_lsns=None, tx_ordinals=None):
            keep(self, batch, commit_lsns, tx_ordinals)
            part = self.parts[-1]
            n = len(part[0]) // 2
            self.parts[-1] = tuple(
                p[:n] if hasattr(p, "__len__") else p for p in part)

        cls._keep = half
    elif fault == "altered_value":
        import numpy as np

        from etl_tpu.ops.engine import DeviceDecoder

        complete = DeviceDecoder._complete

        def altered(self, *args, **kw):
            batch = complete(self, *args, **kw)
            col = batch.columns[2]
            data = np.array(col.data)
            data[0] += 1
            col.data = data
            return batch

        DeviceDecoder._complete = altered
    else:
        raise SystemExit(f"unknown fault {fault!r}; one of {FAULTS}")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    fault = argv[argv.index("--fault") + 1]
    del argv[argv.index("--fault"):argv.index("--fault") + 2]
    plant(fault)
    return harness.main(argv)


if __name__ == "__main__":
    sys.exit(main())
