"""Real-decode serving data plane (slow tier: jit compiles).

ISSUE 3 tentpole coverage: the elastic driver runs against the *jitted*
``decode_step`` (measured, not simulated, per-replica times), KV pages
live as device-resident ``SeqKV`` shards, and a GLB migration window
moves sequence metadata + device KV together with zero lost sequences.
"""
import numpy as np
import pytest

import jax

from repro.serving import (DecodeEngine, ElasticServingDriver, RealDecodeSim,
                           SeqKV, serving_config)

pytestmark = pytest.mark.slow   # jit-compiling tier


@pytest.fixture(scope="module")
def engine():
    """One shared engine so the whole module reuses a warm jit cache."""
    return DecodeEngine(serving_config(n_layers=2, d_model=64, d_ff=128,
                                       vocab_size=256), s_cache=32)


class TestDecodeEngine:
    def test_measured_step_advances_tokens(self, engine):
        kvs = [jax.device_put(engine.new_seq(8)) for _ in range(3)]
        before = [np.asarray(kv.state["pos"]).item() for kv in kvs]
        dt = engine.decode_batch(kvs)
        assert dt > 0.0                      # wall clock, not a model
        for kv, b in zip(kvs, before):
            assert np.asarray(kv.state["pos"]).item() == b + 1
            assert kv.token.shape == (1, 1) and kv.on_device()

    def test_work_multiplier_really_runs(self, engine):
        kvs = [jax.device_put(engine.new_seq(8))]
        t1 = min(engine.decode_batch(kvs) for _ in range(3))
        t4 = min(engine.decode_batch(kvs, work=8) for _ in range(3))
        assert t4 > 2.0 * t1                 # 8x the compute, measured

    def test_bucket_padding_keeps_results(self, engine):
        """Padding to a bucket must not perturb the real sequences."""
        a = [jax.device_put(engine.new_seq(4)) for _ in range(2)]
        b = [jax.device_put(engine.new_seq(4)) for _ in range(2)]
        for kv_a, kv_b in zip(a, b):         # identical start states
            kv_b.state = jax.tree_util.tree_map(lambda x: x, kv_a.state)
            kv_b.token = kv_a.token
        engine.decode_batch(a)               # bucket 2
        engine.decode_batch(b + [jax.device_put(engine.new_seq(4))])  # 4→pad
        np.testing.assert_array_equal(np.asarray(a[0].token),
                                      np.asarray(b[0].token))


class TestRealDataPlane:
    def test_migration_moves_device_kv_zero_lost(self, engine):
        sim = RealDecodeSim(n_replicas=4, slots=16, work=(1, 1, 4, 1),
                            arrival_rate=3.0, glb_period=4, seed=1,
                            engine=engine).run(24)
        d = sim.driver
        assert d.lost() == 0
        st = d.glb.stats
        assert st.rebalances > 0 and st.bytes_moved > 0
        # seq + device-KV pairs stayed together through every window
        for p in d.group.members:
            assert sorted(d.seqs.keys(p)) == sorted(d.kv.keys(p))
            for v in d.kv.handle(p).values():
                assert isinstance(v, SeqKV) and v.on_device()
        # the EWMA was fed by measured times: the slow replica's traffic
        # weight pushed sequences off it
        assert d.seqs.local_size(2) < np.mean(
            [d.seqs.local_size(p) for p in d.group.members if p != 2])

    def test_failure_rehomes_device_kv(self, engine):
        sim = RealDecodeSim(n_replicas=4, slots=16, arrival_rate=3.0,
                            fail_at={8: 1}, glb_period=4, seed=2,
                            engine=engine).run(20)
        d = sim.driver
        assert d.evicted == [1] and 1 not in d.group.members
        assert d.lost() == 0 and d.rehomed_seqs > 0
        for p in d.group.members:
            for v in d.kv.handle(p).values():
                assert v.on_device()

    def test_decode_round_requires_engine(self):
        d = ElasticServingDriver(2)
        with pytest.raises(ValueError, match="engine"):
            d.decode_round()

    def test_device_transport_moves_kv_without_host_bounce(self, engine):
        """ISSUE 5: with ``transport="device"`` the KV migration windows
        encode ``SeqKV`` pages device-side and ship them through the
        jitted ``all_to_all`` — pairs stay intact, pages stay device-
        resident, nothing is lost, and the transport's wire counters
        prove the exchange actually ran."""
        from repro.core import DeviceTransport

        sim = RealDecodeSim(n_replicas=4, slots=48, preload=(0, 24),
                            arrival_rate=2.0, glb_period=3, seed=1,
                            engine=engine, transport="device").run(12)
        d = sim.driver
        assert isinstance(d.transport, DeviceTransport)
        assert d.lost() == 0
        assert d.glb.stats.rebalances > 0
        assert d.transport.lifetime.exchanges >= 1
        assert d.transport.lifetime.row_bytes > 0
        for p in d.group.members:
            assert sorted(d.seqs.keys(p)) == sorted(d.kv.keys(p))
            for v in d.kv.handle(p).values():
                assert v.on_device()

    def test_throughput_positive_and_tokens_counted(self, engine):
        sim = RealDecodeSim(n_replicas=2, slots=8, arrival_rate=2.0,
                            seed=3, engine=engine).run(10)
        assert sim.tokens > 0
        assert sim.throughput() > 0
