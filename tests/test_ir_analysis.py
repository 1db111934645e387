"""etl-lint IR tier (ISSUE 16): falsifiability + determinism + wiring.

Falsifiability: every one of the six compiled-program contracts must
FIRE on a deliberately-violating program — a contract that cannot fail
verifies nothing. The clean repo-wide gate (the catalog passing all
contracts, single-device and on the forced-8-shard mesh) is
`TestRepoCatalogClean`; before it each checker sees a program built to
break it.

Determinism: two runs over the same layout set must produce
byte-identical findings (fingerprints, ordering) and path sets —
including through the forced-8-shard mesh subprocess, whose findings
round-trip JSON.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from etl_tpu.analysis.ir import (IR_CONTRACT_NAMES, IR_NAMESPACE,  # noqa: E402
                                 contracts)
from etl_tpu.analysis.ir.catalog import (ProgramDescriptor,  # noqa: E402
                                         _decoder, build_catalog,
                                         default_schemas, layout_tag)
from etl_tpu.analysis.ir.runner import (analyze_descriptor,  # noqa: E402
                                        analyze_local)

REPO = Path(__file__).resolve().parent.parent


def _host_specs(i: int = 0) -> tuple:
    return _decoder(default_schemas()[i][1])._host_specs()


def _avals(specs, R: int):
    from etl_tpu.ops.engine import program_example_avals

    return program_example_avals(specs, R)


# ---------------------------------------------------------------------------
# falsifiability — one deliberately-bad program per contract
# ---------------------------------------------------------------------------

class TestContractsFire:
    def test_host_callback_fires(self):
        def bad(bmat, lengths):
            fixed = jax.pure_callback(
                lambda x: x, jax.ShapeDtypeStruct(bmat.shape, bmat.dtype),
                bmat)
            return fixed.astype(jnp.uint32).sum()

        jaxpr = jax.jit(bad).trace(*_avals(_host_specs(), 64)).jaxpr
        hits = contracts.check_host_callback(jaxpr)
        assert hits, "pure_callback in the jaxpr must fire the contract"
        assert hits[0][0] == "pure_callback"

    def test_host_callback_clean_on_real_program(self):
        from etl_tpu.ops.engine import lower_program

        fn, avals, _ = lower_program(_host_specs(), 64)
        assert contracts.check_host_callback(fn.trace(*avals).jaxpr) == []

    def test_donation_declared_on_cpu_fires(self):
        from etl_tpu.ops.engine import lower_program

        # the engine never declares donation on CPU; force it — the
        # lowering drops the aliasing, and the contract must say so
        _, _, lowered = lower_program(_host_specs(), 64, donate=True)
        text = lowered.as_text()
        hits = contracts.check_donation(text, True, "cpu")
        assert hits and hits[0][0] == "declared-on-cpu"
        # same artifact judged as an accelerator claim: declared but
        # never realized
        hits = contracts.check_donation(text, True, "tpu")
        assert hits and hits[0][0] == "declared-not-realized"
        # and the production CPU policy (declared=False) is clean
        assert contracts.check_donation(text, False, "cpu") == []

    def test_widening_fires(self):
        def bad(x):
            return x.astype(jnp.float64).sum()

        with jax.enable_x64(True):
            jaxpr = jax.jit(bad).trace(
                jax.ShapeDtypeStruct((64,), np.float32)).jaxpr
        hits = contracts.check_widening(jaxpr)
        assert hits, "f64 conversion under x64 must fire the contract"
        assert any("float64" in d for d, _ in hits)

    def test_output_budget_fires(self):
        n_words, R = 4, 4096
        good = [jax.ShapeDtypeStruct((n_words, R), np.uint32)]
        assert contracts.check_output_budget(
            good, n_words, R, filtered=False, n_shards=0) == []
        # one extra per-row f32 vector blows the budget
        bad = good + [jax.ShapeDtypeStruct((R,), np.float32)]
        hits = contracts.check_output_budget(
            bad, n_words, R, filtered=False, n_shards=0)
        assert hits and "budget" in hits[0][0]

    def test_output_budget_filter_metadata_allowed(self):
        n_words, R, shards = 4, 4096, 8
        outs = [jax.ShapeDtypeStruct((n_words, R), np.uint32),
                jax.ShapeDtypeStruct((R // 32,), np.uint32),   # keep mask
                jax.ShapeDtypeStruct((shards,), np.int32),     # counts
                jax.ShapeDtypeStruct((shards,), np.int32)]     # shard_bad
        assert contracts.check_output_budget(
            outs, n_words, R, filtered=True, n_shards=shards) == []

    def test_canonical_dedup_fires(self):
        from etl_tpu.ops.engine import lower_program
        from etl_tpu.ops.program_store import canonical_plan

        # heterogeneous layout: column order changes the program, so
        # bypassing canonicalization (exact vs reversed EXACT specs)
        # must produce different IR — the failure mode the contract
        # exists to catch
        specs = _host_specs(1)
        rev = tuple(reversed(specs))
        assert canonical_plan(specs).specs == canonical_plan(rev).specs
        text_a = lower_program(specs, 64)[2].as_text()
        text_b = lower_program(rev, 64)[2].as_text()
        hits = contracts.check_canonical_dedup(text_a, text_b)
        assert hits and hits[0][0] == "permutation-lowering-differs"
        # the canonical twins themselves are byte-identical
        canon = canonical_plan(specs).specs
        assert contracts.check_canonical_dedup(
            lower_program(canon, 64)[2].as_text(),
            lower_program(canonical_plan(rev).specs, 64)[2].as_text()) == []

    def test_collective_fires(self):
        # a replicated out_sharding forces an all-gather; needs a
        # multi-device backend, so probe in a forced-8 subprocess (this
        # process's backend is already initialized single-device)
        script = (
            "import jax, numpy as np, json, sys\n"
            "from jax.sharding import Mesh, NamedSharding, "
            "PartitionSpec as P\n"
            "sys.path.insert(0, '.')\n"
            "from etl_tpu.analysis.ir import contracts\n"
            "mesh = Mesh(np.array(jax.devices()), ('sp',))\n"
            "f = jax.jit(lambda x: x * 2,\n"
            "            in_shardings=(NamedSharding(mesh, P('sp')),),\n"
            "            out_shardings=NamedSharding(mesh, P()))\n"
            "low = f.lower(jax.ShapeDtypeStruct((4096,), np.float32))\n"
            "hits = contracts.check_collectives(low.compile().as_text())\n"
            "print(json.dumps([d for d, _ in hits]))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = \
                flags + " --xla_force_host_platform_device_count=8"
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=300,
                              env=env, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        hits = json.loads(proc.stdout.strip().splitlines()[-1])
        assert "all-gather" in hits

    def test_findings_carry_ir_namespace_and_fingerprint(self):
        # a violating descriptor produces findings on the reserved
        # programs/ namespace with the standard fingerprint shape
        specs = _host_specs(1)
        rev = tuple(reversed(specs))
        desc = ProgramDescriptor(tag=layout_tag(specs), specs=specs,
                                 row_capacity=64, variant="host",
                                 dedup_twin=rev)
        findings = analyze_descriptor(desc, {})
        dedup = [f for f in findings if f.rule == "ir-canonical-dedup"]
        assert dedup, "exact-spec twin must trip the dedup contract"
        f = dedup[0]
        assert f.path.startswith(IR_NAMESPACE)
        assert f.fingerprint == \
            f"{f.rule}|{f.path}|{f.scope}|{f.detail}"
        assert f.rule in IR_CONTRACT_NAMES


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

class TestDeterminism:
    def test_single_device_pass_is_byte_identical(self):
        runs = []
        for _ in range(2):
            findings, paths = analyze_local(row_buckets=(256,))
            runs.append((json.dumps([f.to_dict() for f in findings],
                                    sort_keys=True),
                         tuple(paths)))
        assert runs[0] == runs[1]
        # and the catalog itself enumerates identically
        a = [(d.path, d.scope) for d in build_catalog(row_buckets=(256,))]
        b = [(d.path, d.scope) for d in build_catalog(row_buckets=(256,))]
        assert a == b and a == sorted(a)

    def test_mesh_subprocess_is_byte_identical(self):
        from etl_tpu.analysis.ir.runner import run_mesh_subprocess

        runs = []
        for _ in range(2):
            findings, paths = run_mesh_subprocess()
            runs.append((json.dumps([f.to_dict() for f in findings],
                                    sort_keys=True),
                         tuple(paths)))
        assert runs[0] == runs[1]
        assert runs[0][1], "mesh pass must enumerate mesh variants"
        # and the repo's mesh variants hold every contract
        assert json.loads(runs[0][0]) == []


class TestRepoCatalogClean:
    def test_every_enumerable_program_holds_every_contract(self):
        """`python -m etl_tpu.analysis --programs`: every canonical
        layout the catalog enumerates, at the production row buckets,
        lowered through the production jit constructor, violates none of
        the six contracts (the baseline holds no programs/ entry). The
        mesh variants are held by the subprocess test above."""
        findings, paths = analyze_local()
        assert paths, "the catalog enumerated no program"
        assert [f.to_dict() for f in findings] == []


# ---------------------------------------------------------------------------
# program-store persist gate (satellite: refuse to cache a violating
# executable)
# ---------------------------------------------------------------------------

def _gate_key(tag: str) -> tuple:
    """A well-formed program-cache key (the decode keys' 7 slots) for a
    single-device, non-host program: the store reads a key's placement
    and mesh slots when it reloads."""
    return (8, ("ir-gate-test", tag), False, None, False, None, False)


class TestPersistGate:
    @pytest.fixture(autouse=True)
    def _store(self, tmp_path):
        from etl_tpu.ops import program_store

        program_store.reset_for_tests()
        program_store.configure(str(tmp_path))
        yield program_store
        program_store.configure(None)
        program_store.reset_for_tests()

    def test_violating_program_not_persisted(self, _store, tmp_path):
        def bad(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        key = _gate_key("bad")
        args = (np.zeros((8,), dtype=np.float32),)
        fn = _store.acquire(key, lambda: jax.jit(bad), args)
        # still served (decode never regresses on a lint result) ...
        np.testing.assert_array_equal(np.asarray(fn(*args)), args[0])
        # ... but never cached: a fresh load must miss
        assert _store.try_load(key, record_absent=False) is None

    def test_clean_program_persists(self, _store):
        key = _gate_key("good")
        args = (np.zeros((8,), dtype=np.float32),)
        fn = _store.acquire(key, lambda: jax.jit(lambda x: x + 1), args)
        np.testing.assert_array_equal(np.asarray(fn(*args)), args[0] + 1)
        assert _store.try_load(key, record_absent=False) is not None

    def test_gate_reports_callback_violation(self, _store):
        def bad(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        jitted = jax.jit(bad)
        args = (np.zeros((8,), dtype=np.float32),)
        lowered = jitted.lower(*args)
        problems = _store.persist_contract_violations(
            _gate_key("k"), jitted, lowered, args)
        assert any("ir-host-callback" in p for p in problems)


# ---------------------------------------------------------------------------
# CLI wiring + cross-tier baseline staleness
# ---------------------------------------------------------------------------

class TestCliWiring:
    def test_list_rules_with_programs_includes_contracts(self, capsys):
        from etl_tpu.analysis.cli import main

        assert main(["--list-rules", "--programs"]) == 0
        out = set(capsys.readouterr().out.split())
        assert set(IR_CONTRACT_NAMES) <= out

    def test_mesh_requires_programs(self, capsys):
        from etl_tpu.analysis.cli import main

        assert main(["--mesh"]) == 2

    def test_stale_ir_baseline_entry_reported(self, tmp_path, capsys,
                                              monkeypatch):
        """Satellite: a baseline entry in the programs/ namespace whose
        fingerprint no tier can produce anymore (layout gone, or the
        finding migrated between tiers) must surface as stale when the
        IR tier runs — and stay filtered when it does not."""
        from etl_tpu.analysis import cli
        from etl_tpu.analysis.ir import runner as ir_runner

        baseline = tmp_path / "baseline.json"
        baseline.write_text(json.dumps({
            "version": 1,
            "entries": {
                "ir-host-callback|programs/gone-00000000|host-r4096|"
                "pure_callback": {"count": 1},
            },
        }))
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        # IR pass enumerates some OTHER program, produces no findings
        monkeypatch.setattr(
            ir_runner, "analyze_programs",
            lambda mesh=False, row_buckets=None:
                ([], ["programs/elsewhere-11111111"]))
        rc = cli.main(["--check-baseline", "--programs",
                       "--baseline", str(baseline), str(clean)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "programs/gone-00000000" in out
        # without the IR tier the entry is out of scope: not stale
        rc = cli.main(["--check-baseline",
                       "--baseline", str(baseline), str(clean)])
        assert rc == 0
