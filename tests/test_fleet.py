"""etl-fleet unit + integration coverage: spec document semantics,
per-pipeline actuation journals, quota placement, the pure diff, the
level-triggered reconciler (tick / converge / hold / resume across both
crash windows), the simulated runtime's idempotence + delivery
invariants, and the three policy plugins on the shared signal bus.

The 100-pipeline kill-mid-roll proof lives in `python -m etl_tpu.chaos
--fleet`; `TestHundredPipelines` holds the 100-pipeline converge ledger
in ticks; the rest of this file pins the pieces those compose."""

import pytest

from etl_tpu.autoscale.signals import ShardSignals, SignalFrame
from etl_tpu.fleet import (MAX_SHARDS_PER_PIPELINE, STATUS_ABORTED,
                           STATUS_APPLIED, STATUS_PENDING, VERB_CREATE,
                           VERB_DELETE, VERB_RESIZE, ActuationJournal,
                           AdaptiveAckDepthPolicy, AdmissionWeightPolicy,
                           FleetReconciler, FleetSignalBus, FleetSpec,
                           PidLagPolicy, PipelineSpec, SimulatedFleetRuntime,
                           TenantQuota, diff_fleet, place_fleet,
                           seeded_fleet_spec)
from etl_tpu.models.errors import ErrorKind, EtlError
from etl_tpu.store.memory import MemoryStore


def pipe(pid, tenant="acme", k=1, **kw) -> PipelineSpec:
    return PipelineSpec(pipeline_id=pid, tenant_id=tenant,
                        shard_count=k, **kw)


def frame(tick, lag_bytes, k=1) -> SignalFrame:
    return SignalFrame(tick=tick, at_s=float(tick),
                       shards=tuple(ShardSignals(shard=i,
                                                 lag_bytes=lag_bytes // k)
                                    for i in range(k)))


class TestFleetSpec:
    def test_validate_rejects_duplicates_and_bad_counts(self):
        with pytest.raises(EtlError) as e:
            FleetSpec(pipelines=(pipe(1), pipe(1))).validate()
        assert e.value.kind is ErrorKind.CONFIG_INVALID
        with pytest.raises(EtlError):
            pipe(1, k=0).validate()
        with pytest.raises(EtlError):
            pipe(1, k=MAX_SHARDS_PER_PIPELINE + 1).validate()
        with pytest.raises(EtlError):
            FleetSpec(quotas={"t": TenantQuota(max_shards=-1)}).validate()
        with pytest.raises(EtlError):
            FleetSpec(quotas={"t": TenantQuota(slo_weight=0)}).validate()

    def test_with_edit_bumps_version_and_rewrites(self):
        spec = FleetSpec(spec_version=4, pipelines=(pipe(1), pipe(2, k=3)))
        edited = spec.with_edit(remove=[1], add=[pipe(5, k=2)],
                                resize={2: 1})
        assert edited.spec_version == 5
        assert [p.pipeline_id for p in edited.pipelines] == [2, 5]
        assert edited.by_id()[2].shard_count == 1
        # the original document is untouched (frozen value semantics)
        assert spec.by_id()[2].shard_count == 3

    def test_json_round_trip(self):
        spec = FleetSpec(
            spec_version=9,
            pipelines=(pipe(3, tenant="g", k=2, destination="clickhouse",
                            profile="tiny_txs", config={"x": 1}),),
            quotas={"g": TenantQuota(max_shards=5, slo_weight=0.5)})
        assert FleetSpec.from_json(spec.to_json()) == spec
        assert FleetSpec.from_json(None) == FleetSpec()


class TestActuationJournal:
    def test_open_settle_pending_applied(self):
        j = ActuationJournal()
        rec = j.open(verb=VERB_CREATE, from_k=0, to_k=2, spec_version=1)
        assert rec.decision_id == 1 and j.next_id == 2
        assert j.pending() == rec
        j.settle(rec.decision_id, STATUS_APPLIED)
        assert j.pending() is None
        assert [r.decision_id for r in j.applied()] == [1]

    def test_bounded_history_keeps_id_counter(self):
        j = ActuationJournal(max_entries=4)
        for i in range(10):
            rec = j.open(verb=VERB_RESIZE, from_k=1, to_k=2,
                         spec_version=1)
            j.settle(rec.decision_id, STATUS_APPLIED)
        assert len(j.entries) == 4
        assert j.next_id == 11
        back = ActuationJournal.from_json(j.to_json())
        assert back.next_id == 11 and len(back.entries) == 4

    def test_satisfied_by_is_the_observed_target_test(self):
        j = ActuationJournal()
        rec = j.open(verb=VERB_DELETE, from_k=3, to_k=0, spec_version=2)
        assert rec.satisfied_by(0) and not rec.satisfied_by(3)
        assert rec.status == STATUS_PENDING


class TestPlacement:
    def test_unlimited_tenants_get_their_ask(self):
        spec = FleetSpec(pipelines=(pipe(1, k=3), pipe(2, k=2)))
        assert place_fleet(spec) == {1: 3, 2: 2}

    def test_quota_clamps_in_id_order_floor_one_shard(self):
        spec = FleetSpec(
            pipelines=(pipe(1, k=4), pipe(2, k=4), pipe(3, k=4)),
            quotas={"acme": TenantQuota(max_shards=6)})
        # every pipeline keeps 1, surplus (3) dealt id-first
        assert place_fleet(spec) == {1: 4, 2: 1, 3: 1}

    def test_zero_max_shards_means_unlimited(self):
        spec = FleetSpec(pipelines=(pipe(1, k=4),),
                         quotas={"acme": TenantQuota(max_shards=0)})
        assert place_fleet(spec) == {1: 4}

    def test_seeded_spec_quotas_visibly_bite(self):
        spec = seeded_fleet_spec(7, 100)
        targets = place_fleet(spec)
        asked = {p.pipeline_id: p.shard_count for p in spec.pipelines}
        clamped = [pid for pid in targets if targets[pid] < asked[pid]]
        assert clamped, "seeded quotas must clamp someone"
        assert all(k >= 1 for k in targets.values())


class TestDiff:
    def test_verb_order_deletes_creates_resizes(self):
        targets = {2: 3, 4: 1, 5: 2}
        observed = {1: 2, 2: 1, 5: 2}
        actions = diff_fleet(targets, observed)
        assert [(a.verb, a.pipeline_id, a.from_k, a.to_k)
                for a in actions] == [
            (VERB_DELETE, 1, 2, 0),
            (VERB_CREATE, 4, 0, 1),
            (VERB_RESIZE, 2, 1, 3),
        ]

    def test_steady_state_diffs_to_nothing(self):
        assert diff_fleet({1: 2}, {1: 2}) == ()
        assert diff_fleet({}, {}) == ()


class TestSimulatedRuntime:
    async def test_verbs_are_idempotent(self):
        rt = SimulatedFleetRuntime(seed=3)
        await rt.create_pipeline(pipe(1, k=2, profile="tiny_txs"))
        ledger = list(rt.pipelines[1].committed)
        await rt.create_pipeline(pipe(1, k=2, profile="tiny_txs"))
        assert rt.pipelines[1].committed == ledger  # no re-seed
        await rt.resize_pipeline(pipe(1, k=2, profile="tiny_txs"))
        assert rt.pipelines[1].rolls == 0  # same-K resize no-ops
        await rt.delete_pipeline(9)  # absent: state no-op
        assert await rt.list_pipelines() == {1: 2}
        assert rt.violations() == []

    async def test_roll_redelivers_bounded_tail(self):
        rt = SimulatedFleetRuntime(seed=3)
        await rt.create_pipeline(pipe(1, k=1, profile="insert_heavy"))
        await rt.resize_pipeline(pipe(1, k=3, profile="insert_heavy"))
        p = rt.pipelines[1]
        assert p.rolls == 1
        assert max(p.delivered.values()) == 2  # tail dup, within budget
        assert rt.violations() == []
        # a phantom delivery IS a violation the model catches
        p.delivered["phantom:1:0"] = 1
        assert rt.violations()


async def converged_reconciler(seed=7, n=20):
    store = MemoryStore()
    runtime = SimulatedFleetRuntime(seed=seed)
    spec = seeded_fleet_spec(seed, n)
    await store.update_fleet_spec(spec.to_json())
    rec = FleetReconciler(store=store, runtime=runtime,
                          scheduler=_StubScheduler())
    ticks = await rec.converge()
    return store, runtime, spec, rec, ticks


class _StubScheduler:
    def __init__(self):
        self.weights = {}

    def set_slo_weight(self, tenant, weight):
        self.weights[tenant] = weight


class TestReconciler:
    async def test_converges_from_empty_in_one_working_tick(self):
        store, runtime, spec, rec, ticks = await converged_reconciler()
        assert ticks == 1
        assert await runtime.list_pipelines() == place_fleet(spec)
        # every actuation is backed 1:1 by an APPLIED journal record
        journals = [ActuationJournal.from_json(d) for d in
                    (await store.get_fleet_journals()).values()]
        assert sum(len(j.applied()) for j in journals) \
            == len(runtime.actuation_log)
        assert all(j.pending() is None for j in journals)
        assert runtime.violations() == []

    async def test_edit_absorbed_and_slo_weights_fed(self):
        store, runtime, spec, rec, _ = await converged_reconciler()
        edited = spec.with_edit(remove=[1], resize={5: 6},
                                add=[pipe(900, tenant="tenant-burst",
                                          k=2, profile="tiny_txs")])
        await store.update_fleet_spec(edited.to_json())
        assert await rec.converge() == 1
        observed = await runtime.list_pipelines()
        assert observed == place_fleet(edited)
        assert 1 not in observed and observed[900] == 2
        assert 1 in runtime.retired
        # quota SLO weights reached the scheduler via the spec document
        sched = rec._scheduler
        for tenant, quota in edited.quotas.items():
            assert sched.weights[tenant] == quota.slo_weight
        assert runtime.violations() == []

    async def test_pending_journal_holds_the_pipeline(self):
        store, runtime, spec, rec, _ = await converged_reconciler()
        # a crashed coordinator's pending record holds pipeline 5
        # mid-roll (5's tenant is unclamped, so the resize survives
        # placement and actually diffs)
        j = ActuationJournal.from_json(await store.get_fleet_journal(5))
        j.open(verb=VERB_RESIZE, from_k=1, to_k=9,
               spec_version=spec.spec_version)
        await store.update_fleet_journal(5, j.to_json())
        await store.update_fleet_spec(
            spec.with_edit(resize={5: 9}).to_json())
        before = len(runtime.actuation_log)
        result = await rec.tick()
        assert result.held == [5] and result.applied == []
        assert not result.converged
        assert len(runtime.actuation_log) == before  # held = no verbs

    async def test_resume_settles_crash_after_actuation(self):
        """Fleet already shows the target: journal-only settle, ZERO
        runtime calls — the no-double-actuation half."""
        store, runtime, spec, rec, _ = await converged_reconciler()
        target = spec.pipelines[0].pipeline_id
        observed_k = (await runtime.list_pipelines())[target]
        j = ActuationJournal.from_json(await store.get_fleet_journal(target))
        pend = j.open(verb=VERB_RESIZE, from_k=1, to_k=observed_k,
                      spec_version=spec.spec_version)
        await store.update_fleet_journal(target, j.to_json())
        before = len(runtime.actuation_log)
        settled = await rec.resume()
        assert [(r.decision_id, r.status) for r in settled] \
            == [(pend.decision_id, STATUS_APPLIED)]
        assert len(runtime.actuation_log) == before
        assert await rec.resume() == []  # idempotent

    async def test_resume_redrives_crash_before_actuation(self):
        store, runtime, spec, rec, _ = await converged_reconciler()
        target = spec.pipelines[0].pipeline_id
        want = (await runtime.list_pipelines())[target] + 3
        j = ActuationJournal.from_json(await store.get_fleet_journal(target))
        j.open(verb=VERB_RESIZE, from_k=1, to_k=want,
               spec_version=spec.spec_version)
        await store.update_fleet_journal(target, j.to_json())
        await store.update_fleet_spec(
            spec.with_edit(resize={target: want}).to_json())
        before = len(runtime.actuation_log)
        settled = await rec.resume()
        assert [r.status for r in settled] == [STATUS_APPLIED]
        assert len(runtime.actuation_log) == before + 1  # exactly one
        assert (await runtime.list_pipelines())[target] == want
        assert await rec.resume() == []

    async def test_resume_aborts_when_spec_moved_on(self):
        store, runtime, spec, rec, _ = await converged_reconciler()
        target = spec.pipelines[0].pipeline_id
        j = ActuationJournal.from_json(await store.get_fleet_journal(target))
        j.open(verb=VERB_RESIZE, from_k=1, to_k=40,
               spec_version=spec.spec_version)
        await store.update_fleet_journal(target, j.to_json())
        await store.update_fleet_spec(
            spec.with_edit(remove=[target]).to_json())
        before = len(runtime.actuation_log)
        settled = await rec.resume()
        assert [r.status for r in settled] == [STATUS_ABORTED]
        assert len(runtime.actuation_log) == before
        # the next converge deletes the stray against the new truth
        await rec.converge()
        assert target not in await runtime.list_pipelines()
        assert runtime.violations() == []


class TestHundredPipelines:
    """The fleet the docs promise (docs/fleet.md): 100 seeded pipelines
    onto an empty simulated fleet, then one add/remove/resize edit —
    counted in working ticks and journal records, never in seconds."""

    TICKS_MAX = 3

    async def _drive(self, seed: int) -> dict:
        store = MemoryStore()
        runtime = SimulatedFleetRuntime(seed=seed)
        spec = seeded_fleet_spec(seed, 100)
        await store.update_fleet_spec(spec.to_json())
        rec = FleetReconciler(store=store, runtime=runtime)
        ticks = await rec.converge(max_ticks=self.TICKS_MAX + 1)
        edited = spec.with_edit(remove=[1, 2], resize={10: 6, 11: 1},
                                add=[pipe(101, tenant="tenant-edit", k=2)])
        await store.update_fleet_spec(edited.to_json())
        edit_ticks = await rec.converge(max_ticks=self.TICKS_MAX + 1)
        journals = [ActuationJournal.from_json(d) for d in
                    (await store.get_fleet_journals()).values()]
        return {
            "ticks": (ticks, edit_ticks),
            "applied": sum(len(j.applied()) for j in journals),
            "pending": [p for j in journals
                        if (p := j.pending()) is not None],
            "actuations": list(runtime.actuation_log),
            "observed": await runtime.list_pipelines(),
            "targets": place_fleet(edited),
            "violations": runtime.violations(),
        }

    async def test_converge_ledger_and_same_seed_trace(self):
        first = await self._drive(7)
        assert all(1 <= t <= self.TICKS_MAX for t in first["ticks"])
        # zero double actuations: every runtime call is backed 1:1 by
        # an applied journal record, and nothing is left pending
        assert len(first["actuations"]) == first["applied"] >= 100
        assert first["pending"] == []
        assert first["observed"] == first["targets"]
        assert 101 in first["observed"] and 1 not in first["observed"]
        assert first["violations"] == []
        # the actuation trace is a function of the seed
        assert (await self._drive(7))["actuations"] == first["actuations"]
        assert (await self._drive(8))["actuations"] != first["actuations"]


class TestSignalBus:
    def test_pid_recommends_scale_up_for_lagging_pipeline_only(self):
        bus = FleetSignalBus()
        pid_policy = PidLagPolicy()
        bus.register(pid_policy)
        for t in range(3):
            bus.publish(1, frame(t, 256 * 1024 * 1024, k=2))  # lagging
            bus.publish(2, frame(t, 1024, k=2))  # healthy
            bus.step()
        assert pid_policy.recommendations[1] > 2
        assert 2 not in pid_policy.recommendations

    def test_pid_integral_is_wind_up_clamped(self):
        bus = FleetSignalBus()
        pid_policy = PidLagPolicy()
        bus.register(pid_policy)
        cap = pid_policy.config.max_shards
        for t in range(50):  # a LONG sustained surge
            bus.publish(1, frame(t, 1 << 40, k=2))
            bus.step()
        assert pid_policy.recommendations[1] <= cap

    def test_ack_depth_tracks_measured_latency(self):
        class _Window:
            limit = None

            def set_limit(self, v):
                self.limit = v

        window = _Window()
        reads = [(24, 24 * 0.4)]  # mean 0.4s over 0.05s flushes -> 9
        bus = FleetSignalBus()
        policy = AdaptiveAckDepthPolicy(
            window_of=lambda pid: window,
            histogram_read=lambda: reads[-1])
        bus.register(policy)
        bus.publish(1, frame(0, 0))
        assert len(bus.step()) == 1
        # the epsilon fencepost: 0.4/0.05 is 8.000…02 in binary — depth
        # must be ceil(8)+1 = 9, not 10
        assert window.limit == 9
        # unchanged histogram: held (state IS the applied depth)
        bus.publish(1, frame(1, 0))
        assert bus.step() == []
        # latency falls -> depth follows
        reads.append((100, 100 * 0.05))
        bus.publish(1, frame(2, 0))
        bus.step()
        assert window.limit == 2

    def test_ack_depth_cold_histogram_is_held(self):
        bus = FleetSignalBus()
        policy = AdaptiveAckDepthPolicy(
            window_of=lambda pid: None,
            histogram_read=lambda: (3, 0.9))  # < min_samples
        bus.register(policy)
        bus.publish(1, frame(0, 0))
        assert bus.step() == []

    def test_admission_weight_base_and_lag_boost(self):
        sched = _StubScheduler()
        bus = FleetSignalBus()
        spec = FleetSpec(
            spec_version=1,
            pipelines=(pipe(1, tenant="hot", k=1),
                       pipe(2, tenant="cold", k=1)),
            quotas={"hot": TenantQuota(slo_weight=1.5),
                    "cold": TenantQuota(slo_weight=0.5)})
        bus.bind_spec(spec)
        policy = AdmissionWeightPolicy(bus, scheduler=sched)
        bus.register(policy)
        bus.publish(1, frame(0, 256 * 1024 * 1024))  # over the boost bar
        bus.publish(2, frame(0, 1024))
        bus.step()
        assert sched.weights["hot"] == pytest.approx(3.0)  # 1.5 * 2
        assert sched.weights["cold"] == pytest.approx(0.5)
        # unchanged signals: weights are held, not re-applied
        bus.publish(1, frame(1, 256 * 1024 * 1024))
        bus.publish(2, frame(1, 1024))
        assert bus.step() == []

    def test_drop_forgets_history_and_state(self):
        bus = FleetSignalBus()
        pid_policy = PidLagPolicy()
        bus.register(pid_policy)
        bus.publish(1, frame(0, 1 << 30))
        bus.step()
        bus.drop(1)
        assert bus.step() == []
        assert ("pid_lag", 1) not in bus._state
