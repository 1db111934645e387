"""Property-based differential decode tests + slot lag queries."""

import random

import pytest

from etl_tpu.models import Oid
from etl_tpu.testing.property import (GENERATORS, PropertyRunner,
                                      generate_value)
from tests.test_ops_decode import assert_batches_equal, decode_both


class TestPropertyDecode:
    """CPU-decode ≡ device-decode over randomized typed values
    (reference tests/value_roundtrip.rs strategy)."""

    OIDS = list(GENERATORS.keys())

    def test_differential_random_schemas(self):
        runner = PropertyRunner(budget_s=4.0, seed=20260728)

        def case(rng: random.Random):
            n_cols = rng.randint(1, 6)
            oids = [rng.choice(self.OIDS) for _ in range(n_cols)]
            n_rows = rng.randint(1, 40)
            rows = [[generate_value(rng, oid).text for oid in oids]
                    for _ in range(n_rows)]
            dev, cpu = decode_both(oids, rows)
            assert_batches_equal(dev, cpu)

        runner.run(case)
        assert runner.cases_run >= 3

    def test_seed_replay_reproduces_failure(self):
        runner = PropertyRunner(budget_s=0.5, seed=42)
        seen = []

        def failing(rng: random.Random):
            v = rng.randint(0, 10**9)
            seen.append(v)
            if len(seen) == 3:
                raise ValueError("boom")

        with pytest.raises(AssertionError) as ei:
            runner.run(failing)
        assert "seed 44" in str(ei.value)  # base 42 + case index 2
        # replay: same seed → same value
        replay_rng = random.Random(44)
        assert replay_rng.randint(0, 10**9) == seen[2]


class TestSlotLag:
    async def test_lag_query_over_wire(self):
        from etl_tpu.postgres.lag import query_slot_lag
        from etl_tpu.postgres.wire import PgWireConnection
        from etl_tpu.testing.fake_pg_server import FakePgServer
        from tests.test_pipeline_e2e import make_db

        db = make_db()
        server = FakePgServer(db)
        await server.start()
        try:
            conn = PgWireConnection(host="127.0.0.1", port=server.port,
                                    database="postgres", user="etl")
            await conn.connect()
            # create a slot, advance WAL, observe lag
            await conn.query(
                'CREATE_REPLICATION_SLOT "supabase_etl_apply_9" '
                "LOGICAL pgoutput (SNAPSHOT 'export')")
            async with db.transaction() as tx:
                tx.insert(16384, ["999", "lag", "0"])
            metrics = await query_slot_lag(conn)
            assert len(metrics) == 1
            m = metrics[0]
            assert m.slot_name == "supabase_etl_apply_9"
            assert m.confirmed_flush_lag_bytes > 0
            assert m.wal_status == "reserved"
            db.invalidate_slot("supabase_etl_apply_9")
            metrics = await query_slot_lag(conn)
            assert metrics[0].wal_status == "lost"
            await conn.close()
        finally:
            await server.stop()
