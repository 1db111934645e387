"""Measured device break-even for decode routing.

`DeviceDecoder.DEVICE_MIN_ROWS` started life as a constant tuned by hand.
This module measures the two quantities that constant was standing in
for, once per process:

  - the accelerator round trip: wall time of dispatch + compute + fetch
    for a trivial jitted program at two payload sizes, solved as
    ``t(n) = fixed_s + n / bytes_per_s`` (the link's latency AND its
    bandwidth);
  - the host-XLA decode rate, normalized per dense column, from a real
    decode of a synthetic 4-int-column staged batch on the host CPU
    backend (the competing path for mid-size batches).

`DeviceDecoder` then solves, per schema, for the row count where the
device path starts winning:

    R / host_rows_per_s  >=  fixed_s + R * bytes_per_row / bytes_per_s

No separate accelerator (CPU-only hosts, the test mesh) → `measure()`
returns None and callers keep the static default; the routing question
is moot there because "device" and "host" are the same backend. On an
accelerator backend a probe that fails raises: a process that cannot
reach its own chip has nothing to route to, and `Pipeline.start` is
where that surfaces.

Reference parity: the reference has no analogue — its NCCL path is
always-on.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import threading
import time

import numpy as np

log = logging.getLogger("etl_tpu.ops.autotune")

# probe payload sizes for the round-trip fit: far enough apart that the
# bandwidth term is observable over the fixed cost
_PROBE_SMALL = 256 * 1024
_PROBE_LARGE = 8 * 1024 * 1024
_PROBE_REPS = 3

# synthetic host-rate probe: 4 int64 columns × one mid-size bucket
_HOST_PROBE_ROWS = 16_384
_HOST_PROBE_COLS = 4

# never route batches this small to a separate device, whatever the
# probe says
_FLOOR_ROWS = 4096


@dataclasses.dataclass(frozen=True)
class DeviceCostModel:
    """Per-process measurement of the decode routing trade."""

    fixed_s: float  # device dispatch+fetch fixed cost (seconds)
    bytes_per_s: float  # effective host↔device link bandwidth
    host_col_rows_per_s: float  # host-XLA decode rate × dense columns
    backend: str

    def device_min_rows(self, n_dense: int, bytes_per_row: float,
                        default: int) -> int:
        """Smallest row count where the device round trip beats the host
        path for a schema with `n_dense` device-parsed columns moving
        `bytes_per_row` over the link (upload + packed fetch)."""
        if n_dense <= 0:
            return default
        host_s_per_row = n_dense / self.host_col_rows_per_s
        link_s_per_row = bytes_per_row / self.bytes_per_s
        margin = host_s_per_row - link_s_per_row
        if margin <= 0:
            # the link can't even stream the bytes as fast as the host
            # decodes — the device never wins on throughput alone; batches
            # still go at the static default (huge batches overlap enough
            # dispatches for pipelining to change the picture)
            return default
        want = int(self.fixed_s / margin) + 1
        return max(_FLOOR_ROWS, want)


_MEASURED: "list[DeviceCostModel | None] | None" = None
# `measure()` runs on the event loop (first decoder built mid-stream)
# AND in prewarm's executor thread; the lock makes the probe
# single-flight — the loser of the race waits for the winner's model
# instead of re-running a multi-second probe and tearing `_MEASURED`
_MEASURE_LOCK = threading.Lock()


def _fit_round_trip(device) -> tuple[float, float]:
    """min-of-reps wall time for a trivial program at two sizes → solve
    t(n) = a + n/bw. min not mean: host-clock noise only ever slows a
    repetition."""
    import jax

    fn = jax.jit(lambda x: x + np.uint8(1))

    def timed(n: int) -> float:
        buf = np.zeros(n, dtype=np.uint8)
        # warm this shape's program + transfer path
        np.asarray(fn(jax.device_put(buf, device)))
        best = float("inf")
        for _ in range(_PROBE_REPS):
            t0 = time.perf_counter()
            np.asarray(fn(jax.device_put(buf, device)))
            best = min(best, time.perf_counter() - t0)
        return best

    t_small, t_large = timed(_PROBE_SMALL), timed(_PROBE_LARGE)
    bw = (_PROBE_LARGE - _PROBE_SMALL) / max(t_large - t_small, 1e-9)
    fixed = max(t_small - _PROBE_SMALL / bw, 1e-6)
    return fixed, bw


def _measure_host_rate() -> float:
    """Host-XLA decode rate on a synthetic staged batch, in
    column-rows/second (schemas scale it by their dense column count)."""
    from ..models import (ColumnSchema, Oid, ReplicatedTableSchema,
                          TableName, TableSchema)
    from .engine import DeviceDecoder
    from .staging import stage_copy_chunk

    schema = ReplicatedTableSchema.with_all_columns(TableSchema(
        1, TableName("etl", "autotune_probe"),
        tuple(ColumnSchema(f"c{i}", Oid.INT8)
              for i in range(_HOST_PROBE_COLS))))
    line = b"\t".join(str(1234567 + i).encode()
                      for i in range(_HOST_PROBE_COLS))
    chunk = (line + b"\n") * _HOST_PROBE_ROWS
    staged = stage_copy_chunk(chunk, _HOST_PROBE_COLS)
    # device_min_rows above the probe size pins the host path; mesh=None
    # keeps the probe off any multi-device routing; telemetry=False keeps
    # the warm+reps probe decodes out of the routed-rows counters — the
    # device-share honesty metric must reflect real traffic only
    dec = DeviceDecoder(schema, device_min_rows=1 << 30, mesh=None,
                        telemetry=False)
    dec.decode(staged)  # compile + warm
    best = float("inf")
    for _ in range(_PROBE_REPS):
        t0 = time.perf_counter()
        dec.decode(staged)
        best = min(best, time.perf_counter() - t0)
    return _HOST_PROBE_ROWS * _HOST_PROBE_COLS / best


def measure(force: bool = False) -> DeviceCostModel | None:
    """Probe once per process (dominated by the probe programs'
    compiles); None when there is no separate accelerator, and an
    exception when there is one and the probe cannot reach it.
    Single-flight under `_MEASURE_LOCK`: safe to race from the loop and
    prewarm's executor thread."""
    global _MEASURED
    if _MEASURED is not None and not force:
        return _MEASURED[0]
    with _MEASURE_LOCK:
        if _MEASURED is not None and not force:
            return _MEASURED[0]
        import jax

        backend = jax.default_backend()
        if backend == "cpu":
            _MEASURED = [None]
            return None
        # a failing probe raises and caches nothing: the next caller
        # probes again instead of routing on a constant no measurement
        # backs
        fixed, bw = _fit_round_trip(jax.devices()[0])
        host_rate = _measure_host_rate()
        model = DeviceCostModel(fixed_s=fixed, bytes_per_s=bw,
                                host_col_rows_per_s=host_rate,
                                backend=backend)
        log.info(
            "device cost model: fixed=%.1fms bw=%.1fMB/s host=%.2fM "
            "col-rows/s (%s)", fixed * 1e3, bw / 1e6, host_rate / 1e6,
            backend)
        _MEASURED = [model]
        return model


async def prewarm() -> DeviceCostModel | None:
    """Measure from async code WITHOUT blocking the event loop.

    `measure()` jit-compiles a probe program and moves 2x8 MiB over the
    host<->device link. The round-5 advisor caught it running
    synchronously inside the apply loop when the first `DeviceDecoder`
    was constructed mid-stream (engine.py device_min_rows resolution),
    stalling keepalives for every table. `Pipeline.start()` awaits this
    before spawning workers, so the per-process cache is hot by the time
    any decoder is built on the loop.
    """
    if _MEASURED is not None:
        return _MEASURED[0]
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(None, measure)


def resolve_device_min_rows(n_dense: int, bytes_per_row: float,
                            default: int) -> int:
    """The measured routing threshold for one schema, or `default` when
    no measurement is possible."""
    model = measure()
    if model is None:
        return default
    return model.device_min_rows(n_dense, bytes_per_row, default)
