"""IR-tier driver: lower every cataloged program, run the contracts.

Ordering is load-bearing: descriptors are enumerated sorted, findings
are emitted per-descriptor in contract-catalog order and then sorted by
the same (path, line, col, rule) key the AST tier uses, and the mesh
subprocess serializes findings as JSON dicts the parent reconstructs —
two runs over the same layout set are byte-identical (fingerprints,
chains, ordering), which the determinism tests pin.

The forced-mesh pass runs in a SUBPROCESS because an already-initialized
jax backend cannot grow devices: the parent may hold a single-device CPU
backend, so `--mesh` spawns `python -m etl_tpu.analysis
--programs-mesh-inner` with XLA_FLAGS forcing an 8-way host platform,
and that child enumerates ONLY the mesh-sharded variants.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from ..findings import Finding
from . import contracts
from .catalog import ProgramDescriptor, build_catalog

_REPO_ROOT = Path(__file__).resolve().parents[3]

#: forced device count for the mesh subprocess — the 8 virtual devices
#: tests/conftest.py forces, i.e. one pod-slice's worth of shards
MESH_FORCED_DEVICES = 8

_MESH_SUBPROCESS_TIMEOUT_S = 600


class IrAnalysisError(RuntimeError):
    """Analyzer failure (not a lint finding): exit-code-2 territory."""


def _lower(desc: ProgramDescriptor, cache: dict):
    """(jitted, avals, lowered, stablehlo_text) for one descriptor, via
    the engine's own constructor. Cached on the full jit signature: the
    host and device variants of one layout collapse to one lowering on
    CPU (identical constructor args), which is exactly the production
    sharing the canonical-program design promises."""
    from ...ops.egress import lower_egress_program
    from ...ops.engine import lower_program

    key = (desc.specs, desc.row_capacity, desc.nibble, desc.use_pallas,
           desc.n_shards, desc.donate,
           desc.pred.fingerprint() if desc.pred is not None else None,
           desc.egress)
    hit = cache.get(key)
    if hit is None:
        if desc.egress is not None:
            fn, avals, lowered = lower_egress_program(
                desc.specs, desc.egress, desc.row_capacity,
                mesh=desc.mesh)
        else:
            fn, avals, lowered = lower_program(
                desc.specs, desc.row_capacity, nibble=desc.nibble,
                use_pallas=desc.use_pallas, mesh=desc.mesh,
                donate=desc.donate, pred=desc.pred)
        hit = (fn, avals, lowered, lowered.as_text())
        cache[key] = hit
    return hit


def _twin_text(desc: ProgramDescriptor, cache: dict) -> str:
    twin = ProgramDescriptor(
        tag=desc.tag, specs=desc.dedup_twin,
        row_capacity=desc.row_capacity, variant=desc.variant,
        nibble=desc.nibble, use_pallas=desc.use_pallas, mesh=desc.mesh,
        donate=desc.donate, pred=desc.pred)
    return _lower(twin, cache)[3]


def analyze_descriptor(desc: ProgramDescriptor, cache: dict,
                       backend: "str | None" = None) -> list:
    """All contract findings for one program descriptor."""
    import jax

    from ...ops.bitpack import layout_for_specs

    fn, avals, lowered, text = _lower(desc, cache)
    backend = backend or jax.default_backend()
    findings: list[Finding] = []

    def emit(rule: str, pairs) -> None:
        for detail, message in pairs:
            findings.append(Finding(rule=rule, path=desc.path, line=1,
                                    col=0, scope=desc.scope,
                                    detail=detail, message=message))

    if desc.hot_loop:
        jaxpr = fn.trace(*avals).jaxpr
        emit("ir-host-callback", contracts.check_host_callback(jaxpr))
        emit("ir-widening", contracts.check_widening(jaxpr))
    emit("ir-donation",
         contracts.check_donation(text, desc.donate, backend))
    out_avals = jax.tree_util.tree_leaves(lowered.out_info)
    if desc.egress is not None:
        from ...ops.egress import plan_for_specs

        plan = plan_for_specs(desc.specs, desc.egress)
        emit("ir-egress-output-budget",
             contracts.check_egress_output_budget(
                 out_avals, desc.row_capacity, plan.total_width,
                 len(plan.slots)))
    else:
        n_words = layout_for_specs(desc.specs).n_words
        emit("ir-output-budget",
             contracts.check_output_budget(out_avals, n_words,
                                           desc.row_capacity,
                                           filtered=desc.pred is not None,
                                           n_shards=desc.n_shards))
    if desc.n_shards:
        # collectives only materialize in the COMPILED module — the
        # lowered StableHLO still carries sharding annotations, not ops
        emit("ir-collective",
             contracts.check_collectives(lowered.compile().as_text()))
    if desc.dedup_twin is not None:
        emit("ir-canonical-dedup",
             contracts.check_canonical_dedup(text, _twin_text(desc, cache)))
    return findings


def _finding_sort_key(f: Finding):
    # same composite the AST tier's analyze_paths sorts on, extended
    # with (scope, detail) — IR findings share line/col
    return (f.path, f.line, f.col, f.rule, f.scope, f.detail)


def analyze_local(*, mesh=None, row_buckets=None) -> tuple:
    """Run the tier in-process over the catalog for `mesh` (None =
    single-device variants). Returns (findings, program_paths) — paths
    cover every ENUMERATED program, clean or not, so `--check-baseline`
    can treat the whole namespace as scanned."""
    try:
        descriptors = build_catalog(mesh=mesh, row_buckets=row_buckets)
    except Exception as e:
        raise IrAnalysisError(f"program enumeration failed: {e}") from e
    cache: dict = {}
    findings: list[Finding] = []
    paths: list[str] = []
    for desc in descriptors:
        paths.append(desc.path)
        try:
            findings.extend(analyze_descriptor(desc, cache))
        except Exception as e:
            raise IrAnalysisError(
                f"lowering {desc.path} [{desc.scope}] failed: {e}") from e
    findings.sort(key=_finding_sort_key)
    return findings, sorted(set(paths))


def run_mesh_inner() -> dict:
    """The `--programs-mesh-inner` payload: enumerate ONLY the mesh
    variants on this (forced-multi-device) backend and return the JSON
    document the parent merges."""
    from ...parallel.mesh import decode_mesh

    mesh = decode_mesh()
    if mesh is None or mesh.size < 2:
        raise IrAnalysisError(
            "mesh inner pass started without a multi-device backend "
            "(XLA_FLAGS --xla_force_host_platform_device_count missing?)")
    findings, paths = analyze_local(mesh=mesh)
    return {"findings": [f.to_dict() for f in findings],
            "paths": paths, "n_shards": mesh.size}


def _finding_from_dict(d: dict) -> Finding:
    return Finding(rule=d["rule"], path=d["path"], line=d["line"],
                   col=d["col"], scope=d["scope"], detail=d["detail"],
                   message=d["message"], chain=tuple(d.get("chain", ())),
                   chain_sites=tuple(tuple(s) for s
                                     in d.get("chain_sites", ())))


def run_mesh_subprocess() -> tuple:
    """Spawn the forced-8-shard child and reconstruct its findings."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_"
                            f"device_count={MESH_FORCED_DEVICES}").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "etl_tpu.analysis", "--programs-mesh-inner"],
        capture_output=True, text=True, env=env, cwd=str(_REPO_ROOT),
        timeout=_MESH_SUBPROCESS_TIMEOUT_S)
    if proc.returncode != 0:
        raise IrAnalysisError(
            f"mesh subprocess failed (exit {proc.returncode}): "
            f"{proc.stderr.strip()[-2000:]}")
    try:
        doc = json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as e:
        raise IrAnalysisError(
            f"mesh subprocess emitted no JSON document: {e}; "
            f"stdout tail: {proc.stdout[-500:]!r}") from e
    return ([_finding_from_dict(d) for d in doc.get("findings", ())],
            list(doc.get("paths", ())))


def analyze_programs(*, mesh: bool = False, row_buckets=None) -> tuple:
    """The CLI entry: single-device pass in-process, plus the forced
    mesh subprocess when `mesh`. Returns (findings, program_paths),
    both deterministically sorted."""
    findings, paths = analyze_local(row_buckets=row_buckets)
    if mesh:
        mesh_findings, mesh_paths = run_mesh_subprocess()
        findings = findings + mesh_findings
        paths = paths + mesh_paths
    findings.sort(key=_finding_sort_key)
    return findings, sorted(set(paths))
