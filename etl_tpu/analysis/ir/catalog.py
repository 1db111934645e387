"""Program enumeration for the IR tier.

A `ProgramDescriptor` is everything needed to lower ONE decode program
exactly the way production dispatch would: canonical specs, row bucket,
engine selection (XLA / pallas), nibble packing, mesh, donation policy,
and the compiled row filter for fused-filter variants. The catalog
enumerates descriptors from three sources:

  * the built-in schema catalog below — a kind-diverse set covering
    every DEVICE_KIND family, the nibble fast path, the pallas engine
    envelope, and a filtered table, so the tier has real coverage even
    on a fresh checkout with an empty program store;
  * the program store's *observed signatures* — host-program cache keys
    recorded from live dispatches, folded in so layouts actually seen in
    production are re-verified on every lint run;
  * permuted-column twins per multi-column schema, feeding the
    ir-canonical-dedup contract.

Descriptor tags (`programs/<kinds>-<hash8>`) derive from the canonical
specs via the program store's stable repr, so the finding namespace is
identical across processes, machines, and the forced-mesh subprocess.
"""

from __future__ import annotations

import dataclasses
import hashlib


@dataclasses.dataclass
class ProgramDescriptor:
    """One lowerable decode program (see module docstring)."""
    tag: str            # stable layout tag, e.g. "i32x3-1f2e3d4c"
    specs: tuple        # canonical (col_index, kind, gather_w, bit_w) specs
    row_capacity: int
    variant: str        # host|device|nibble|pallas|filtered|mesh|mesh-filtered
    nibble: bool = False
    use_pallas: bool = False
    mesh: object = None           # jax.sharding.Mesh | None
    donate: bool = False
    pred: object = None           # predicate.CompiledRowFilter | None
    hot_loop: bool = True
    source: str = "schema"        # schema | observed
    #: wire-encoder name (ops/egress.py) — set on egress-program
    #: descriptors, which lower the SECOND fused stage (words → wire
    #: text) instead of a decode program
    egress: str = None
    #: permuted-twin canonical specs for ir-canonical-dedup (None = skip)
    dedup_twin: tuple = None

    @property
    def path(self) -> str:
        return f"programs/{self.tag}"

    @property
    def scope(self) -> str:
        return f"{self.variant}-r{self.row_capacity}"

    @property
    def n_shards(self) -> int:
        return self.mesh.size if self.mesh is not None else 0


def layout_tag(specs: tuple) -> str:
    """`<kind-counts>-<hash8>`: human-greppable prefix + collision-proof
    stable hash of the canonical specs."""
    from ...ops.program_store import _stable_repr

    counts: dict = {}
    for _, kind, _, _ in specs:
        name = kind.name.lower()
        counts[name] = counts.get(name, 0) + 1
    kinds = "+".join(f"{k}x{n}" for k, n in sorted(counts.items()))
    digest = hashlib.sha256(_stable_repr(specs).encode()).hexdigest()[:8]
    return f"{kinds or 'empty'}-{digest}"


def _table(name: str, cols) -> "object":
    from ...models import (ReplicatedTableSchema, TableName, TableSchema)

    oid = 90000 + (hash(name) % 1000)
    return ReplicatedTableSchema.with_all_columns(TableSchema(
        oid, TableName("public", name), tuple(cols)))


def default_schemas() -> list:
    """(name, schema) pairs the tier always covers. Chosen for span, not
    volume: every DEVICE_KIND family appears, one schema is nibble-
    eligible (all-int, even widths), one fits the pallas envelope
    (ΣW ≤ MAX_TOTAL_WIDTH), one exceeds it, and one mixes dense with
    host-object columns the way real tables do."""
    from ...models import ColumnSchema, Oid

    pgbench = _table("pgbench_accounts", (
        ColumnSchema("aid", Oid.INT4, nullable=False, primary_key_ordinal=1),
        ColumnSchema("bid", Oid.INT4),
        ColumnSchema("abalance", Oid.INT4),
        ColumnSchema("filler", Oid.BPCHAR, modifier=88)))
    # every remaining DEVICE_KIND family + object spill (numeric/text)
    kinds_wide = _table("lint_kinds_wide", (
        ColumnSchema("id", Oid.INT8, nullable=False, primary_key_ordinal=1),
        ColumnSchema("flag", Oid.BOOL),
        ColumnSchema("small", Oid.INT2),
        ColumnSchema("ratio", Oid.FLOAT4),
        ColumnSchema("total", Oid.FLOAT8),
        ColumnSchema("born", Oid.DATE),
        ColumnSchema("at_time", Oid.TIME),
        ColumnSchema("created", Oid.TIMESTAMP),
        ColumnSchema("updated", Oid.TIMESTAMPTZ),
        ColumnSchema("amount", Oid.NUMERIC),
        ColumnSchema("note", Oid.TEXT)))
    # nibble-eligible: int/date kinds only — exercises the halved-upload
    # program variant
    nibble = _table("lint_nibble", (
        ColumnSchema("a", Oid.INT4, nullable=False, primary_key_ordinal=1),
        ColumnSchema("b", Oid.INT8),
        ColumnSchema("d", Oid.DATE)))
    return [("pgbench_accounts", pgbench),
            ("lint_kinds_wide", kinds_wide),
            ("lint_nibble", nibble)]


def filtered_schema():
    """(name, schema, compiled-filter-producing decoder schema): pgbench
    with the `abalance < 0` publication row filter —
    device-supported, referenced column dense."""
    from ...ops.predicate import parse_row_filter

    name, schema = default_schemas()[0]
    return ("pgbench_filtered",
            schema.with_row_predicate(parse_row_filter("abalance < 0")))


def _decoder(schema):
    from ...ops.engine import DeviceDecoder

    return DeviceDecoder(schema, mesh=None, telemetry=False,
                         device_min_rows=1 << 30,
                         nonblocking_compile=True)


def _device_specs(dec):
    """The device-path width signature for an all-NULL batch at minimum
    gather widths — the deterministic signature the tier verifies (real
    batches bucket up from here; the program structure is identical)."""
    from ...ops.staging import synthetic_staged_batch

    staged = synthetic_staged_batch(len(dec.schema.replicated_columns), 64)
    widths = dec._widths(staged)
    return dec._specs(staged, widths), widths


def build_catalog(*, mesh=None, row_buckets=None,
                  include_observed: bool = True) -> list:
    """All descriptors for one run, deterministically ordered.

    `mesh=None` enumerates the single-device set (host + device + nibble
    + pallas + filtered variants per schema). A mesh enumerates ONLY the
    mesh-sharded variants — the forced-8-shard subprocess runs with just
    those, and the parent runs the single-device set, so no program is
    checked twice."""
    from ...ops.egress import ENCODER_JSON, ENCODER_TSV, plan_for_specs
    from ...ops.engine import _donation_supported
    from ...ops.pallas_kernel import pallas_supported
    from ...ops.program_store import canonical_plan, load_observed

    buckets = tuple(row_buckets) if row_buckets else (4096,)
    donate_dev = _donation_supported()
    out: list[ProgramDescriptor] = []
    seen: set = set()

    def add(desc: ProgramDescriptor):
        key = (desc.specs, desc.row_capacity, desc.variant, desc.nibble,
               desc.use_pallas, desc.n_shards,
               desc.pred.fingerprint() if desc.pred is not None else None,
               desc.egress)
        if key in seen:
            return
        seen.add(key)
        out.append(desc)

    for name, schema in default_schemas() + [filtered_schema()]:
        dec = _decoder(schema)
        host_specs = dec._host_specs()
        if not host_specs:
            continue
        pred = dec._row_filter
        if pred is not None and not pred.device_supported:
            pred = None
        dev_specs, widths = _device_specs(dec)
        host_plan = canonical_plan(host_specs)
        dev_plan = canonical_plan(dev_specs)
        # permuted twin: reversed column order must canonicalize to the
        # same layout; the runner lowers both and byte-compares
        twin = canonical_plan(tuple(reversed(host_specs))).specs \
            if len(host_specs) > 1 else None
        # egress programs: the wire-encoding second stage, enumerated
        # per (layout, encoder) exactly as the program store keys them —
        # only for layouts with at least one renderable field
        egress_encoders = [e for e in (ENCODER_TSV, ENCODER_JSON)
                           if pred is None
                           and plan_for_specs(dev_plan.specs, e)
                           is not None]
        for bucket in buckets:
            if mesh is not None:
                if bucket % mesh.size:
                    continue
                add(ProgramDescriptor(
                    tag=layout_tag(dev_plan.specs), specs=dev_plan.specs,
                    row_capacity=bucket,
                    variant="mesh-filtered" if pred is not None else "mesh",
                    mesh=mesh, donate=donate_dev, pred=pred))
                for enc in egress_encoders:
                    add(ProgramDescriptor(
                        tag=layout_tag(dev_plan.specs),
                        specs=dev_plan.specs, row_capacity=bucket,
                        variant=f"mesh-egress-{enc}", mesh=mesh,
                        egress=enc))
                continue
            add(ProgramDescriptor(
                tag=layout_tag(host_plan.specs), specs=host_plan.specs,
                row_capacity=bucket,
                variant="filtered-host" if pred is not None else "host",
                pred=pred, dedup_twin=twin))
            add(ProgramDescriptor(
                tag=layout_tag(dev_plan.specs), specs=dev_plan.specs,
                row_capacity=bucket,
                variant="filtered" if pred is not None else "device",
                donate=donate_dev, pred=pred))
            if pred is None and dec._can_nibble(widths):
                add(ProgramDescriptor(
                    tag=layout_tag(dev_plan.specs), specs=dev_plan.specs,
                    row_capacity=bucket, variant="nibble", nibble=True,
                    donate=donate_dev))
            if pred is None and pallas_supported(dev_plan.specs):
                add(ProgramDescriptor(
                    tag=layout_tag(dev_plan.specs), specs=dev_plan.specs,
                    row_capacity=bucket, variant="pallas",
                    use_pallas=True, donate=donate_dev))
            for enc in egress_encoders:
                add(ProgramDescriptor(
                    tag=layout_tag(dev_plan.specs), specs=dev_plan.specs,
                    row_capacity=bucket, variant=f"egress-{enc}",
                    egress=enc))

    if mesh is None and include_observed:
        # observed host-program signatures: key shape is
        # (row_capacity, canonical_specs, False, None, False, pred_fp,
        #  True) — see engine._host_fn_key. Only unfiltered keys are
        # reconstructable from the fingerprint alone (a pred_fp cannot
        # be turned back into a CompiledRowFilter without its schema).
        for key in load_observed():
            if len(key) != 7 or not key[-1] or key[5] is not None:
                continue
            row_capacity, specs = key[0], key[1]
            if not (isinstance(specs, tuple) and specs
                    and all(isinstance(s, tuple) and len(s) == 4
                            for s in specs)):
                continue
            add(ProgramDescriptor(
                tag=layout_tag(specs), specs=specs,
                row_capacity=row_capacity, variant="host",
                source="observed"))

    out.sort(key=lambda d: (d.path, d.scope, d.source))
    return out
