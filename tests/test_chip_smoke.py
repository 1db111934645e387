"""chip_smoke.py at a tiny size on the CPU backend (Pallas in interpret
mode), so the script cannot rot between chip runs: its data generators,
its oracle comparison, its pipeline phase and its refusal to run off the
chip. The sizes and the chip-only checks (Mosaic compiled the kernel,
rows reached the device) are the chip run's; everything else is the same
code."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402

SEED = 7
ROWS = 300


@pytest.fixture(scope="module")
def accounts():
    return chip_smoke.accounts_case(SEED, ROWS)


@pytest.fixture(scope="module")
def kinds():
    return chip_smoke.kinds_case(SEED, ROWS)


class TestEnginePhase:
    def test_accounts_every_family(self, accounts):
        from etl_tpu.parallel.mesh import default_decode_mesh

        mesh = default_decode_mesh()
        assert mesh is not None and mesh.size == 8  # conftest's devices
        out = chip_smoke.xla_checks(accounts)
        assert 0.3 < out["filter_keep"] < 0.7
        assert out["egress"]["device_fields"] == 3
        assert list(chip_smoke.pallas_checks(accounts)) == ["group0"]
        chip_smoke.xla_checks(accounts, mesh, mesh_min_rows=0)
        sharding = chip_smoke.sharding_check(accounts, mesh, mesh_min_rows=0)
        assert sharding["etl_decode_mesh_shards"] == 8

    def test_kinds_cover_device_kinds_on_xla(self, kinds):
        out = chip_smoke.xla_checks(kinds)
        # bool, 4 ints, date, 2 timestamps render on device; floats and
        # time stay on the host twins
        assert out["egress"]["device_fields"] == 8

    @pytest.mark.slow
    def test_whole_phase(self):
        """Everything the chip run does, kinds through the Pallas kernel
        in its width-bound groups included (~1 min in interpret mode)."""
        out = chip_smoke.engine_phase(SEED, ROWS, ROWS, mesh_min_rows=0)
        assert len(out["kinds"]["pallas"]) >= 2
        assert set(out["kinds"]) == {"rows", "xla", "pallas", "mesh"}

    def test_a_wrong_value_fails_the_phase(self, accounts):
        """The comparison is live: one flipped oracle value is a
        SmokeFailure, not a green run."""
        data = accounts.oracle.columns[2].data
        data[17] ^= 1
        try:
            with pytest.raises(chip_smoke.SmokeFailure, match="CPU codecs"):
                chip_smoke._decode_checks(
                    accounts.schema, accounts.payloads, accounts.oracle,
                    accounts, "accounts/xla", mesh=None)
        finally:
            data[17] ^= 1


class TestPipelinePhase:
    def test_copy_then_cdc_delivers_the_generators_truth(self):
        out = chip_smoke.pipeline_phase(
            SEED, copy_rows=3000, cdc_events=4000, tx_rows=100,
            warm_waves=(100, 500))
        assert out["copy"]["rows_per_second"] > 0
        window = out["streamed_window"]
        # the CPU backend has no device to route to: the window runs the
        # warmed host programs, and only a batch too small for them
        # reaches the per-row oracle (the phase holds that equal itself)
        assert window["device_rows"] == 0 == window["programs_compiled"]
        assert window["host_rows"] + window["oracle_rows"] == 4000
        assert window["host_rows"] >= 3000
        assert out["durable_lsn"] >= out["last_commit_lsn"]

    def test_fold_is_order_independent_and_row_sensitive(self):
        import numpy as np

        aid, bid, bal = chip_smoke.accounts_columns(SEED, 1000)
        whole = chip_smoke.fold_columns(aid, bid, bal)
        total: dict = {}
        for part in (slice(600, 1000), slice(0, 600)):
            chip_smoke._add(total, chip_smoke.fold_columns(
                aid[part], bid[part], bal[part]))
        assert total == whole
        swapped = bal.copy()
        swapped[[3, 4]] = swapped[[4, 3]]
        assert chip_smoke.fold_columns(aid, bid, swapped) != whole
        assert np.array_equal(
            chip_smoke.accounts_columns(SEED, 1000)[2], bal)


class TestEntryPoint:
    def test_main_refuses_to_run_off_the_chip(self, capsys):
        assert chip_smoke.main([]) == 3
        captured = capsys.readouterr()
        assert captured.out == "", "no result may be printed off the chip"
        assert "not 'tpu'" in captured.err

    @pytest.mark.parametrize("ok", [True, False])
    def test_last_line_is_the_drivers_object_and_nothing_more(
            self, ok, capsys, monkeypatch):
        """On the chip the last stdout line carries exactly `ok` and
        `device` {platform, kind, count}; the run's record rides the
        summary line before it."""
        import json

        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        monkeypatch.setattr(chip_smoke, "device_record", lambda: device)
        monkeypatch.setattr(
            chip_smoke, "run_phases",
            lambda seed, dev: {"ok": ok, "device": dev, "seed": seed,
                               "wall_seconds": 1.0})
        assert chip_smoke.main([]) == (0 if ok else 1)
        lines = capsys.readouterr().out.splitlines()
        assert json.loads(lines[-1]) == {"ok": ok, "device": device}
        summary = json.loads(lines[-2])
        assert summary["phase"] == "summary" and summary["seed"] == SEED

    def test_a_crash_still_ends_on_ok_false(self, capsys, monkeypatch):
        import json

        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        monkeypatch.setattr(chip_smoke, "device_record", lambda: device)

        def boom(seed, dev):
            raise RuntimeError("mosaic said no")

        monkeypatch.setattr(chip_smoke, "run_phases", boom)
        with pytest.raises(RuntimeError):
            chip_smoke.main([])
        last = capsys.readouterr().out.splitlines()[-1]
        assert json.loads(last) == {"ok": False, "device": device}
