"""Elastic serving runtime: traffic-keyed rebalance convergence, router
consistency across migrations, dead-replica re-homing, multi-collection
windows, the ServingPool admission fix, and the three serving scenarios
(steady, hot spot, failover) end to end."""
import numpy as np
import pytest

from repro.core import (DistArray, DistBag, DistIdMap, GLBConfig,
                        GlobalLoadBalancer, LongRange, MultiCollectionWorkload,
                        PlaceGroup)
from repro.runtime.fault_tolerance import (ElasticWorld, FaultTolerantDriver,
                                           HeartbeatMonitor,
                                           rehome_dead_place)
from repro.serving import (ElasticServingDriver, Router, Sequence,
                           ServingPool, ServingSim, TokenCostModel,
                           TrafficWorkload)


def make_pool(n_places=2, per_place=8, tokens=32):
    """seqs + kv DistIdMaps with `per_place` sequences on each place."""
    g = PlaceGroup(n_places)
    seqs, kv = DistIdMap(g), DistIdMap(g)
    cost = TokenCostModel()
    sid = 0
    for p in g.members:
        seqs.handle(p), kv.handle(p)
        for _ in range(per_place):
            s = Sequence(sid, tokens, max_new=10 ** 9)   # never retires
            seqs.put(p, sid, s)
            kv.put(p, sid, np.zeros((cost.pages(s), 4), np.float32))
            sid += 1
    return g, seqs, kv


# ---------------------------------------------------------------------------
# TrafficWorkload: the traffic-keyed Workload adapter
# ---------------------------------------------------------------------------
class TestTrafficWorkload:
    def test_token_cost_model_pages(self):
        cm = TokenCostModel(page_tokens=16)
        assert cm.pages(Sequence(0, 1)) == 1          # floor of one page
        assert cm.pages(Sequence(0, 16)) == 1
        assert cm.pages(Sequence(0, 17)) == 2
        assert cm.pages(Sequence(0, 30, generated=10)) == 3

    def test_loads_weighted_by_decode_ewma(self):
        _, seqs, kv = make_pool(n_places=2, per_place=8)
        wl = TrafficWorkload(seqs, kv, ema=0.0)  # ema=0: last sample wins
        even = wl.loads()
        assert even[0] == even[1] > 0            # same pages, same ewma
        wl.observe([2.0, 1.0])                   # replica 0 decodes slower
        hot = wl.loads()
        assert hot[0] > hot[1]                   # traffic-keyed, not counts
        assert seqs.local_size(0) == seqs.local_size(1)

    def test_transfer_converts_traffic_to_sequences(self):
        _, seqs, kv = make_pool(n_places=2, per_place=10)
        wl = TrafficWorkload(seqs, kv, min_keep=1)
        loads = wl.loads()
        wl.transfer(((0, 1, int(loads[0] // 2)),))   # ship half the traffic
        assert wl.last_moved_seqs > 0
        assert seqs.local_size(0) + seqs.local_size(1) == 20
        assert seqs.local_size(1) > seqs.local_size(0) >= 1

    def test_kv_pages_ride_the_same_window(self):
        _, seqs, kv = make_pool(n_places=2, per_place=6)
        wl = TrafficWorkload(seqs, kv)
        handle = wl.transfer(((0, 1, int(wl.loads()[0] // 2)),),
                             asynchronous=True)
        handle.finish()
        assert handle.manager.syncs == 1         # one window, both cols
        for p in seqs.group.members:
            assert sorted(seqs.keys(p)) == sorted(kv.keys(p))
        assert kv.global_size() == seqs.global_size() == 12
        # tracked distributions reconciled for both collections
        assert seqs.get_distribution().total == 12
        assert kv.get_distribution().total == 12

    def test_kv_bytes_counts_resident_payloads(self):
        _, seqs, kv = make_pool(n_places=2, per_place=4)
        wl = TrafficWorkload(seqs, kv)
        per_seq = 2 * 4 * 4        # pages(32 tokens) x 4 lanes x float32
        assert wl.kv_bytes_of(0) == 4 * per_seq
        wl.transfer(((0, 1, int(wl.loads()[0])),))
        assert wl.kv_bytes_of(0) + wl.kv_bytes_of(1) == 8 * per_seq
        assert wl.kv_bytes_of(99) == 0   # unknown member

    def test_min_keep_floor(self):
        _, seqs, kv = make_pool(n_places=2, per_place=5)
        wl = TrafficWorkload(seqs, kv, min_keep=3)
        wl.transfer(((0, 1, 10 ** 9),))          # absurd traffic demand
        assert seqs.local_size(0) >= 3


# ---------------------------------------------------------------------------
# convergence: hot replica sheds KV pages (ISSUE acceptance)
# ---------------------------------------------------------------------------
class TestConvergence:
    def test_hotspot_sheds_traffic(self):
        speeds = (1, 1, 1, 1, 1, 0.4, 1, 1)
        sim = ServingSim(n_replicas=8, speeds=speeds, arrival_rate=5,
                         seed=1).run(60)
        d = sim.driver
        assert d.lost() == 0
        pages = np.asarray([d.workload.pages_of(p) for p in d.group.members])
        fast = np.delete(pages, 5)
        assert pages[5] < 0.6 * fast.mean()      # hot replica shed its KV
        assert d.glb.stats.rebalances > 0
        assert d.glb.stats.overlap_fraction > 0.5   # migration overlapped

    def test_beats_no_balance_p95(self):
        # count-based admission isolates *relocation*: with the default
        # traffic-aware policy the no-balance baseline also steers new
        # arrivals off the hot replica, and the two runs nearly tie
        speeds = (1, 1, 1, 1, 1, 0.4, 1, 1)
        kw = dict(n_replicas=8, speeds=speeds, arrival_rate=5, seed=1,
                  admission="count")
        with_lb = ServingSim(**kw).run(60)
        no_lb = ServingSim(balance=False, **kw).run(60)
        p_lb = np.mean(with_lb.window_p95()[-4:])
        p_no = np.mean(no_lb.window_p95()[-4:])
        assert p_lb < p_no * 0.95

    def test_even_traffic_no_churn(self):
        sim = ServingSim(n_replicas=4, arrival_rate=4, seed=0).run(40)
        assert sim.driver.lost() == 0
        # an even cluster should migrate little relative to its pool
        assert sim.driver.workload.migrated_pages < \
            sum(sim.driver.workload.pages_of(p)
                for p in sim.driver.group.members)


# ---------------------------------------------------------------------------
# router consistency across migrations
# ---------------------------------------------------------------------------
class TestRouter:
    def test_dispatch_follows_migrations(self):
        sim = ServingSim(n_replicas=8, speeds=(1, 1, 1, 1, 1, 0.4, 1, 1),
                         arrival_rate=5, seed=3)
        for _ in range(6):                        # reconcile + verify often
            sim.run(8)
            d = sim.driver
            for p in d.group.members:
                for sid in d.seqs.keys(p):
                    assert d.router.owner(sid) == p, \
                        f"router sent {sid} to {d.router.owner(sid)}, " \
                        f"resident on {p}"
        assert sim.driver.glb.stats.rebalances > 0  # migrations did happen

    def test_retired_sequences_unroutable(self):
        sim = ServingSim(n_replicas=4, arrival_rate=4, seed=0).run(40)
        d = sim.driver
        assert len(d.completed) > 0
        for sid in d.completed[:20]:
            assert d.router.owner(sid) is None

    def test_dispatch_batch_matches_scalar_across_migration(self):
        """Router-at-scale satellite: the vectorized table dispatch and
        the per-request path agree — before, across, and after a
        migration window."""
        g, seqs, kv = make_pool(n_places=3, per_place=6)
        wl = TrafficWorkload(seqs, kv)
        router = Router(seqs)
        router.refresh()
        sids = list(range(18)) + [99, -3]          # unknown + nonsense too

        def scalar_owners(r):
            return [o if (o := r.owner(s)) is not None else -1 for s in sids]

        def check():
            ref = Router(seqs)
            ref.refresh()
            want = scalar_owners(ref)
            got = router.dispatch_batch(sids)
            assert got.tolist() == want
            # queue contents mirror the scalar path, in arrival order
            for s in sids:
                ref.dispatch(s)
            for p in seqs.group.members:
                assert router.drain(p) == ref.drain(p)

        check()
        handle = wl.transfer(((0, 1, int(wl.loads()[0] // 2)),),
                             asynchronous=True)
        handle.finish()                            # window delivered
        router.refresh()
        check()
        assert router.batches == 2 and router.routed == 2 * 18
        # unroutable requests parked exactly like the scalar path
        assert len(router.retries) == 2 * 2

    def test_router_refreshes_on_zero_move_windows(self):
        """A balanced cluster plans zero moves, so no delivery barrier
        ever fires — the window boundary itself must still refresh the
        router or new admissions stay unroutable forever."""
        d = ElasticServingDriver(
            2, glb=GLBConfig(period=2, policy="proportional", ema=0.3))
        sids = [d.admit(16, max_new=100) for _ in range(4)]
        for _ in range(4):                 # crosses two window boundaries
            d.step(np.array([1.0, 1.0]))
        assert d.glb.stats.rebalances == 0  # genuinely nothing migrated
        owners = [d.router.dispatch(s) for s in sids]
        assert all(o is not None for o in owners)

    def test_table_base_compacts_retired_prefix(self):
        """The dispatch table covers only the live sid window: retired
        low sids stop costing table space after update_dist."""
        _, seqs, _ = make_pool(n_places=2, per_place=4)   # sids 0..7
        for sid in (0, 1, 2):
            seqs.handle(0).pop(sid)
        seqs.update_dist()
        router = Router(seqs)
        assert router.base == 3
        assert len(router.table) == 5
        assert router.dispatch_batch([3, 7, 0]).tolist() == [0, 1, -1]

    def test_dispatch_batch_masks_dead_replica(self):
        g, seqs, _ = make_pool(n_places=2, per_place=4)
        router = Router(seqs)
        router.refresh()
        dead_sids = seqs.keys(1)
        router.mark_dead(1)                        # table masked in place
        owners = router.dispatch_batch(dead_sids)
        assert (owners == -1).all()
        assert len(router.retries) == len(dead_sids)

    def test_device_table_mirrors_host_table(self):
        import jax

        _, seqs, _ = make_pool(n_places=2, per_place=3)
        router = Router(seqs)
        router.refresh()
        dev = router.device_table()
        assert isinstance(dev, jax.Array)
        np.testing.assert_array_equal(np.asarray(dev), router.table)

    def test_dead_queue_drains_to_retry_then_reroutes(self):
        g, seqs, _ = make_pool(n_places=3, per_place=4)
        router = Router(seqs)
        sid = seqs.keys(1)[0]
        assert router.dispatch(sid, "req") == 1
        router.mark_dead(1)
        assert router.rerouted == 1               # queued request drained
        assert router.owner(sid) is None          # no live owner yet
        # re-home place 1 and refresh: the retry re-dispatches
        rehome_dead_place(g, 1, (seqs,))
        router.refresh()
        new_owner = router.owner(sid)
        assert new_owner in (0, 2)
        assert any(s == sid for s, _ in router.queues[new_owner])


# ---------------------------------------------------------------------------
# dead-replica re-homing (failure-aware placement)
# ---------------------------------------------------------------------------
class TestFailover:
    def test_dead_replica_rehomed_zero_lost(self):
        sim = ServingSim(n_replicas=8, arrival_rate=5, fail_at={20: 3},
                         seed=2).run(60)
        d = sim.driver
        assert d.evicted == [3]
        assert 3 not in d.group.members
        assert d.lost() == 0                      # conservation
        assert d.rehomed_seqs > 0
        assert d.seqs.local_size(3) == 0 if 3 in d.seqs._handles else True
        assert d.glb.stats.places_evicted == 1
        # lifelines rebuilt over survivors only, still connected
        assert 3 not in d.glb.lifelines
        assert all(3 not in nbrs for nbrs in d.glb.lifelines.values())
        reach, frontier = {0}, [0]
        while frontier:
            frontier = [v for u in frontier for v in d.glb.lifelines[u]
                        if v not in reach and not reach.add(v)]
        assert reach == set(d.group.members)

    def test_admission_skips_dead(self):
        sim = ServingSim(n_replicas=4, arrival_rate=2, fail_at={10: 1},
                         seed=0).run(30)
        d = sim.driver
        for _ in range(12):
            sid = d.admit(16, 8)
            assert sid is not None
            owner = d.seqs.get_distribution().owner_of(sid)
            assert owner != 1

    def test_elastic_world_evicts_arrays_and_bags(self):
        g = PlaceGroup(3)
        col = DistArray(g, track=True)
        col.add_chunk(1, LongRange(0, 30), np.arange(30)[:, None] * 1.0)
        for p in g.members:
            col.handle(p)
        bag = DistBag(g)
        for i in range(9):
            bag.put(1, np.float64(i))
        world = ElasticWorld(g)
        new_group = world.evict(1, (col, bag))
        assert new_group.members == (0, 2)
        assert col.global_size() == 30 and bag.global_size() == 9
        assert col.group is new_group and bag.group is new_group
        assert 1 not in col._handles and 1 not in bag._handles
        assert col.get_distribution().total == 30

    def test_fault_tolerant_driver_glb_eviction_path(self):
        """runtime/fault_tolerance wiring: with a GLB attached, a death
        evicts + re-homes instead of checkpoint-rollback."""
        from repro.core import DistArrayWorkload
        g = PlaceGroup(4)
        col = DistArray(g, track=True)
        for p, r in enumerate(LongRange(0, 80).split(4)):
            col.add_chunk(p, r, np.arange(r.start, r.end)[:, None] * 1.0)
        glb = GlobalLoadBalancer(g, DistArrayWorkload(col), GLBConfig())
        world = ElasticWorld(g)
        ft = FaultTolerantDriver(
            n_places=4, ckpt_manager=None,     # must never be touched
            monitor=HeartbeatMonitor(4, timeout_steps=1),
            glb=glb, world=world, glb_collections=(col,))
        state = {"x": 0}
        step_fn = lambda s: {"x": s["x"] + 1}
        for _ in range(3):
            state, info = ft.run_step(state, step_fn, None,
                                      failed_places=(2,))
            if info.get("evicted"):
                break
        assert info["evicted"] == [2]
        assert not info["restored"] and ft.restarts == 0
        assert state["x"] > 0                     # no rollback: kept going
        assert col.global_size() == 80
        assert world.group.members == (0, 1, 3)
        assert glb.alive_members() == (0, 1, 3)


# ---------------------------------------------------------------------------
# multi-collection GLB windows (paper Listing 12, ROADMAP item)
# ---------------------------------------------------------------------------
class TestMultiCollection:
    def _copartitioned(self, n=120, places=4):
        g = PlaceGroup(places)
        prim = DistArray(g, track=True)
        comp = DistArray(g, track=True)
        prim.add_chunk(0, LongRange(0, n), np.arange(n)[:, None] * 1.0)
        comp.add_chunk(0, LongRange(0, n), np.arange(n)[:, None] * 10.0)
        for p in g.members:
            prim.handle(p), comp.handle(p)
        return g, prim, comp

    def test_one_window_carries_both(self):
        g, prim, comp = self._copartitioned()
        wl = MultiCollectionWorkload(prim, (comp,))
        assert wl.layouts_consistent()
        handle = wl.transfer(((0, 2, 40),), asynchronous=True)
        handle.finish()
        assert handle.manager.syncs == 1          # single sync window
        assert wl.layouts_consistent()            # co-residency preserved
        assert prim.local_size(2) == comp.local_size(2) == 40
        assert prim.global_size() == comp.global_size() == 120

    def test_transfer_rejects_diverged_layout(self):
        g, prim, comp = self._copartitioned()
        from repro.core import CollectiveMoveManager
        mm = CollectiveMoveManager(g)
        comp.move_range_at_sync(LongRange(0, 10), 3, mm)
        mm.sync()
        wl = MultiCollectionWorkload(prim, (comp,))
        assert not wl.layouts_consistent()
        with pytest.raises(ValueError, match="diverged"):
            wl.transfer(((0, 2, 40),))

    def test_glb_drives_copartitioned_collections(self):
        g, prim, comp = self._copartitioned()
        glb = GlobalLoadBalancer(
            g, MultiCollectionWorkload(prim, (comp,)),
            GLBConfig(period=1, policy="proportional", asynchronous=False))
        glb.record_all([8.0, 1.0, 1.0, 1.0])
        glb.step()
        glb.finish()
        assert glb.stats.entries_rebalanced > 0
        wl_layout_ok = all(prim.ranges(p) == comp.ranges(p)
                           for p in g.members)
        assert wl_layout_ok
        assert prim.global_size() == comp.global_size() == 120
        assert comp.get_distribution().total == 120


# ---------------------------------------------------------------------------
# ServingPool.admit fix (satellite): alive-only, index→member mapping
# ---------------------------------------------------------------------------
class TestServingPoolAdmission:
    def test_admit_maps_argmin_index_to_member_id(self):
        pool = ServingPool(PlaceGroup(4), slots_per_replica=8)
        for _ in range(8):
            pool.admit(16)
        pool.evict(1)                             # members now (0, 2, 3)
        assert pool.group.members == (0, 2, 3)
        sids = [pool.admit(16) for _ in range(9)]
        assert all(s is not None for s in sids)
        for s in sids:
            assert pool.replica_of(s) in (0, 2, 3)
        # the dead replica holds nothing and is never an admission target
        assert pool.seqs.global_size() == 17
        assert all(pool.seqs.local_size(p) > 0 for p in (0, 2, 3))

    def test_admit_full_pool_rejects(self):
        pool = ServingPool(PlaceGroup(2), slots_per_replica=2)
        assert all(pool.admit(8) is not None for _ in range(4))
        assert pool.admit(8) is None

    def test_step_moves_map_through_members(self):
        pool = ServingPool(PlaceGroup(4), slots_per_replica=32, lb_period=1)
        for _ in range(24):
            pool.admit(16, max_new=100)
        pool.evict(2)
        # survivor 3 is slow: the balancer must move seqs between the
        # surviving member ids, never to/from the evicted place 2
        for _ in range(4):
            pool.step(np.array([1.0, 1.0, 5.0]))
        assert pool.seqs.global_size() == 24
        assert 2 not in pool.seqs._handles
        assert pool.loads().sum() == 24


# ---------------------------------------------------------------------------
# the three serving scenarios end to end: 8 replicas, GLB window every 4
# steps, 8 windows of warm-up (then, for failover, replica 3 dies and 6
# more windows run)
# ---------------------------------------------------------------------------
PERIOD, WARM_W, POST_W = 4, 8, 6
SCENARIOS = {
    "steady": dict(seed=1),
    "hotspot": dict(speeds=(1, 1, 1, 1, 1, 0.4, 1, 1), admission="count",
                    seed=1),
    "failover": dict(fail_at={WARM_W * PERIOD: 3}, seed=2),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_serving_scenario_zero_lost(scenario):
    kw = SCENARIOS[scenario]
    failing = "fail_at" in kw
    windows = WARM_W + (POST_W if failing else 0)
    sim = ServingSim(n_replicas=8, arrival_rate=3.0, glb_period=PERIOD,
                     **kw).run(windows * PERIOD)
    d = sim.driver
    # conservation: every admitted sequence is resident or completed
    assert d.lost() == 0
    if not failing:
        assert d.evicted == [] and len(d.group.members) == 8
        return
    assert 3 not in d.group.members and d.evicted == [3]
    # p95 back within 1.5x of the pre-failure level inside 10 windows
    p95 = sim.window_p95()
    baseline = float(np.mean(p95[WARM_W - 3:WARM_W]))
    post = p95[WARM_W:]
    recovery = next((i + 1 for i, p in enumerate(post)
                     if p <= 1.5 * baseline), None)
    assert recovery is not None and recovery <= 10, (baseline, post)


def test_real_decode_hot_replica_spreads_device_kv():
    """The jitted decode step drives the driver on a skewed residency: a
    hot shard of long-lived sequences pinned to replica 0, which only
    relocation can spread.  Balanced and unbalanced runs share one
    engine and alternate window by window; neither loses a sequence,
    and the balanced run's windows ship device KV with every sequence
    beside its KV."""
    from repro.serving import DecodeEngine, RealDecodeSim
    from repro.serving.decode import serving_config

    engine = DecodeEngine(serving_config(n_layers=1, d_model=32, d_ff=64,
                                         vocab_size=64),
                          s_cache=32, max_batch=4)
    kw = dict(n_replicas=4, slots=24, preload=(0, 16), arrival_rate=2.0,
              max_new_range=(16, 32), glb_period=PERIOD, seed=1,
              engine=engine)
    un = RealDecodeSim(balance=False, **kw)
    ba = RealDecodeSim(**kw)
    for _ in range(4):
        un.run(PERIOD)
        ba.run(PERIOD)
    d = ba.driver
    assert d.lost() == 0 and un.driver.lost() == 0
    assert d.glb.stats.rebalances > 0 and d.glb.stats.bytes_moved > 0
    for p in d.group.members:
        assert sorted(d.seqs.keys(p)) == sorted(d.kv.keys(p))
        assert all(v.on_device() for v in d.kv.handle(p).values())
    assert sum(d.workload.kv_bytes_of(p) for p in d.group.members) > 0
    assert ba.tokens > 0 and un.tokens > 0
