"""Double-buffered relocation windows (ISSUE 4): enqueue/commit split,
window chaining, per-window overlap accounting, wait_counts timeout, and
the phase-1 counts parity between the host and SPMD halves."""
import threading
import time

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core import (
    CollectiveMoveManager, DistArray, DistArrayWorkload, DistIdMap,
    GLBConfig, GlobalLoadBalancer, LongRange, PlaceGroup, spmd_counts,
)
from repro.core.relocation import _pack_by_dest


def make_col(n_places=4, n=120, width=2, skew=None):
    g = PlaceGroup(n_places)
    col = DistArray(g, track=True)
    if skew is None:
        for p, r in enumerate(LongRange(0, n).split(n_places)):
            if r.size:
                col.add_chunk(p, r, np.arange(r.start, r.end)[:, None]
                              * np.ones((1, width)))
    else:
        col.add_chunk(skew, LongRange(0, n),
                      np.arange(n)[:, None] * np.ones((1, width)))
        for p in range(n_places):
            col.handle(p)
    return g, col


def entry_multiset(col, n):
    vals = []
    for p in col.group.members:
        rows, _ = col.to_local_matrix(p)
        if len(rows):
            vals.extend(np.asarray(rows)[:, 0].tolist())
    return sorted(vals)


class GatedArray(DistArray):
    """DistArray whose extractions block on a gate and record call
    order — makes window chaining observable."""

    def __init__(self, group, **kw):
        super().__init__(group, **kw)
        self.gate = threading.Event()
        self.extract_log: list = []

    def _extract_range(self, r, src):
        self.extract_log.append((r.start, r.end))
        self.gate.wait(timeout=10.0)
        return super()._extract_range(r, src)


# ---------------------------------------------------------------------------
# depth-2 pipeline
# ---------------------------------------------------------------------------
class TestPipelineDepth2:
    def test_depth2_matches_depth1_final_state(self):
        finals = []
        for depth in (1, 2):
            g, col = make_col(n=200)
            mm = CollectiveMoveManager(g)
            for w in range(4):
                src, dst = (0, 3) if w % 2 == 0 else (3, 0)
                col.move_at_sync_count(src, 20, dst, mm)
                mm.sync_async(update_dists=(col,), depth=depth)
            mm.drain()
            assert col.global_size() == 200
            finals.append(([col.local_size(p) for p in g.members],
                           entry_multiset(col, 200), mm.syncs))
        assert finals[0] == finals[1]

    def test_depth2_matches_depth1_keyed_pair_hops(self):
        # the serving shape: two co-partitioned DistIdMaps (sequence
        # metadata + KV pages) hand a key block on from replica to
        # replica, one hop a window, by key rule; each hop's source only
        # holds the block once the previous window delivered it
        keys, block = 300, frozenset(range(150))
        finals = []
        for depth in (1, 2):
            g = PlaceGroup(8)
            seqs, kv = DistIdMap(g), DistIdMap(g)
            for p in g.members:
                seqs.handle(p)
                kv.handle(p)
            for k in range(keys):
                seqs.put(0, k, np.full(4, k, np.float32))
                kv.put(0, k, np.full((4, 16), k, np.float32))
            mm = CollectiveMoveManager(g)
            for src in range(3):
                rule = lambda k, s=src: s + 1 if k in block else s  # noqa: E731
                seqs.move_at_sync(src, rule, mm)
                kv.move_at_sync(src, rule, mm)
                mm.sync_async(update_dists=(seqs, kv), depth=depth)
            mm.drain()
            assert seqs.global_size() == keys and kv.global_size() == keys
            for p in g.members:
                assert sorted(seqs.keys(p)) == sorted(kv.keys(p))
                for k in kv.keys(p):
                    assert kv.get(p, k)[0, 0] == seqs.get(p, k)[0] == k
            finals.append([sorted(seqs.keys(p)) for p in g.members])
        assert finals[0] == finals[1]
        assert finals[1][:4] == [list(range(150, keys)), [], [],
                                 sorted(block)]

    def test_depth_bounds_inflight_windows(self):
        g, col = make_col(n=400)
        mm = CollectiveMoveManager(g)
        handles = []
        for w in range(5):
            col.move_at_sync_count(0, 5, 1, mm)
            handles.append(mm.sync_async(update_dists=(col,), depth=2))
        # at most 2 windows in flight: everything older has committed
        unfinished = [h for h in handles if not h.finished]
        assert len(unfinished) <= 2
        assert handles[0].finished and handles[1].finished
        mm.drain()
        assert all(h.finished for h in handles)
        assert mm.syncs == 5
        assert col.global_size() == 400

    def test_windows_commit_fifo(self):
        g, col = make_col(n=400)
        mm = CollectiveMoveManager(g)
        col.move_at_sync_count(0, 10, 1, mm)
        h1 = mm.sync_async(update_dists=(col,), depth=2)
        col.move_at_sync_count(0, 30, 2, mm)
        h2 = mm.sync_async(update_dists=(col,), depth=2)
        h2.finish()
        # committing h2 first still leaves accounting FIFO-consistent:
        # h2's counts are the manager's last committed matrix
        assert h1.finished or not h1.finished  # h1 may still be open
        mm.drain()
        assert h1.finished and h2.finished
        # delivery happened exactly once per window
        assert col.local_size(1) == 110 and col.local_size(2) == 130
        assert col.global_size() == 400

    def test_chained_extraction_waits_for_predecessor(self):
        g = PlaceGroup(4)
        col = GatedArray(g, track=True)
        col.add_chunk(0, LongRange(0, 40),
                      np.arange(40, dtype=np.float64)[:, None])
        for p in g.members:
            col.handle(p)
        mm = CollectiveMoveManager(g)
        col.move_range_at_sync(LongRange(0, 30), 1, mm)
        h1 = mm.sync_async(depth=2)
        col.move_range_at_sync(LongRange(30, 40), 2, mm)
        h2 = mm.sync_async(depth=2)
        time.sleep(0.05)
        # w1 is parked inside the gate; w2's extraction must not have
        # started (it chains behind w1's phase 1)
        assert len(col.extract_log) == 1
        assert col.extract_log[0] == (0, 30)
        assert not h2.counts_ready()
        col.gate.set()
        h1.finish()
        h2.finish()
        assert col.extract_log[1] == (30, 40)
        assert col.local_size(1) == 30 and col.local_size(2) == 10

    def test_chained_key_moves_see_predecessor_deliveries(self):
        """A window whose key-rule moves target entries still in the
        predecessor's flight must wait for that delivery — otherwise the
        rule enumerates the source's keys too early and the move
        silently no-ops (regression: ping-pong windows ended split
        instead of round-tripped)."""
        g = PlaceGroup(4)
        m = DistIdMap(g)
        for p in g.members:
            m.handle(p)
        for k in range(20):
            m.put(0, k, np.float32(k))
        mm = CollectiveMoveManager(g)
        block = frozenset(range(10))
        for w in range(2):
            src, dst = (0, 1) if w % 2 == 0 else (1, 0)
            rule = lambda k, s=src, d=dst: d if k in block else s  # noqa: E731
            m.move_at_sync(src, rule, mm)
            mm.sync_async(update_dists=(m,), depth=2)
        mm.drain()
        # the block went 0 -> 1 and then 1 -> 0: fully round-tripped
        assert [m.local_size(p) for p in g.members] == [20, 0, 0, 0]
        assert sorted(m.keys(0)) == list(range(20))

    def test_chain_links_released_after_delivery(self):
        """Finished windows must not stay pinned through successor
        ``_after`` references (a long-running pipeline would otherwise
        retain every handle ever created)."""
        g, col = make_col(n=400)
        mm = CollectiveMoveManager(g)
        handles = []
        for _ in range(4):
            col.move_at_sync_count(0, 5, 1, mm)
            handles.append(mm.sync_async(update_dists=(col,), depth=2))
        mm.drain()
        assert all(h._after is None for h in handles)

    def test_enqueue_delivers_in_background(self):
        g, col = make_col(n=200)
        mm = CollectiveMoveManager(g)
        col.move_at_sync_count(0, 20, 3, mm)
        h = mm.sync_async(update_dists=(col,))
        h.enqueue()
        deadline = time.time() + 5.0
        while not h._delivered.is_set() and time.time() < deadline:
            time.sleep(0.002)
        # delivered + reconciled before the commit barrier was reached
        assert col.local_size(3) == 70
        assert col.get_distribution().owner_of(10) == 3
        assert not h.finished
        assert mm.syncs == 0          # accounting waits for the commit
        h.finish()
        assert mm.syncs == 1
        assert "t_delivered" in h.trace

    def test_error_in_oldest_window_propagates_at_depth_enforcement(self):
        g, col = make_col(n=100)
        mm = CollectiveMoveManager(g)
        col.move_at_sync_count(0, 10_000, 1, mm)     # will raise in phase 1
        mm.sync_async(depth=2)
        col.move_at_sync_count(1, 5, 2, mm)
        mm.sync_async(depth=2)                       # pipeline has room
        col.move_at_sync_count(2, 5, 3, mm)
        with pytest.raises(ValueError):
            mm.sync_async(depth=2)                   # drains the bad window
        mm.drain()                                   # rest still commits
        assert col.local_size(3) == 30


# ---------------------------------------------------------------------------
# GLB pipeline accounting (satellite: per-window overlap_fraction)
# ---------------------------------------------------------------------------
class TestGLBPipeline:
    def test_pipeline_depth2_conserves_and_accounts_per_window(self):
        g, col = make_col(n=400, skew=0)
        glb = GlobalLoadBalancer(
            g, DistArrayWorkload(col),
            GLBConfig(period=1, policy="proportional", pipeline_depth=2))
        for t in ([9.0, 1.0, 1.0, 1.0], [5.0, 2.0, 1.0, 1.0],
                  [2.0, 2.0, 2.0, 1.0], [1.5, 1.5, 1.5, 1.5]):
            glb.record_all(t)
            glb.step()
        assert glb.has_pending()          # the pipeline really pipelines
        glb.finish()
        assert not glb.has_pending()
        # every launched window was accounted individually
        assert glb.stats.syncs_total == glb.stats.rebalances > 0
        assert 0.0 <= glb.stats.overlap_fraction <= 1.0
        assert col.global_size() == 400
        assert entry_multiset(col, 400) == sorted(float(i)
                                                  for i in range(400))

    def test_overlapped_uses_delivery_start_for_pipelined_windows(self):
        """A double-buffered window whose delivery finished only after
        the commit barrier must not count as overlapped, even though its
        phase 1 beat the (late) barrier — the pre-fix accounting always
        compared against t_finish_enter and would report True."""
        g = PlaceGroup(4)

        class SlowInsert(DistArray):
            def _insert_payload(self, dest, payload):
                time.sleep(0.08)
                super()._insert_payload(dest, payload)

        col = SlowInsert(g, track=True)
        col.add_chunk(0, LongRange(0, 40),
                      np.arange(40, dtype=np.float64)[:, None])
        for p in g.members:
            col.handle(p)
        mm = CollectiveMoveManager(g)
        col.move_at_sync_count(0, 10, 1, mm)
        h = mm.sync_async(update_dists=(col,))
        h.wait_counts()
        h.enqueue()                    # pipelined: delivery pre-barrier
        h.finish()                     # barrier arrives mid-delivery
        assert h.trace["t_enqueue"] < h.trace["t_finish_enter"]
        assert not h.overlapped        # commit had to wait for delivery
        # the plain path still reports phase-1 overlap as before
        mm2 = CollectiveMoveManager(g)
        col.move_at_sync_count(1, 2, 2, mm2)
        h2 = mm2.sync_async(update_dists=(col,))
        h2.wait_counts()
        time.sleep(0.01)
        h2.finish()
        assert h2.overlapped


# ---------------------------------------------------------------------------
# wait_counts timeout (satellite)
# ---------------------------------------------------------------------------
class TestWaitCountsTimeout:
    def test_timeout_returns_none_then_finish_succeeds(self):
        g = PlaceGroup(4)
        col = GatedArray(g, track=True)
        col.add_chunk(0, LongRange(0, 40),
                      np.arange(40, dtype=np.float64)[:, None])
        for p in g.members:
            col.handle(p)
        mm = CollectiveMoveManager(g)
        col.move_range_at_sync(LongRange(0, 10), 2, mm)
        h = mm.sync_async(update_dists=(col,))
        t0 = time.perf_counter()
        counts = h.wait_counts(timeout=0.05)   # phase 1 parked in the gate
        assert counts is None                  # expired, not raised
        assert time.perf_counter() - t0 < 5.0
        assert not h.counts_ready()
        col.gate.set()                         # let phase 1 complete
        h.finish()                             # post-timeout barrier works
        assert h.finished
        counts = h.wait_counts(timeout=1.0)
        assert counts is not None and counts.sum() > 0
        assert col.local_size(2) == 10 and col.local_size(0) == 30

    def test_timeout_zero_is_nonblocking(self):
        g = PlaceGroup(2)
        col = GatedArray(g, track=True)
        col.add_chunk(0, LongRange(0, 10),
                      np.arange(10, dtype=np.float64)[:, None])
        col.handle(1)
        mm = CollectiveMoveManager(g)
        col.move_range_at_sync(LongRange(0, 5), 1, mm)
        h = mm.sync_async()
        assert h.wait_counts(timeout=0.0) is None
        col.gate.set()
        h.finish()


# ---------------------------------------------------------------------------
# phase-1 counts parity: host matrix vs SPMD counts/pack (satellite)
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(n_places=st.integers(2, 6), per_place=st.integers(3, 24),
       width=st.integers(1, 4), seed=st.integers(0, 10 ** 6))
def test_property_spmd_counts_match_host_phase1(n_places, per_place, width,
                                                seed):
    """For a random off-diagonal move set, the host ``_phase1`` byte
    matrix (header-stripped) equals the device ``spmd_counts`` /
    ``_pack_by_dest`` row counts — the two §5.3 accounting surfaces of
    the same exchange.  Diagonal (self-move) entries never reach the
    host wire (PR 3 invariant); on device they are the kept rows."""
    rng = np.random.default_rng(seed)
    n = n_places * per_place
    g = PlaceGroup(n_places)
    col = DistArray(g, track=True)
    for p, r in enumerate(LongRange(0, n).split(n_places)):
        col.add_chunk(p, r, np.arange(r.start, r.end, dtype=np.float64)
                      [:, None] * np.ones((1, width)))
    # random move set: per source, split a random share of its entries
    # over random non-self destinations
    rows_planned = np.zeros((n_places, n_places), np.int64)
    mm = CollectiveMoveManager(g)
    for src in range(n_places):
        budget = int(rng.integers(0, per_place + 1))
        while budget > 0:
            dest = int(rng.integers(0, n_places))
            cnt = int(rng.integers(1, budget + 1))
            if dest != src:
                col.move_at_sync_count(src, cnt, dest, mm)
                rows_planned[src, dest] += cnt
            budget -= cnt
    h = mm.sync_async()
    counts = h.wait_counts(timeout=10.0)
    payloads = h._payloads
    assert np.all(np.diag(counts) == 0)          # self-moves stay local
    # strip the 16-byte per-payload headers to recover row counts
    n_payloads = np.zeros((n_places, n_places), np.int64)
    for _, src, dest, _ in payloads:
        if src != dest:
            n_payloads[src, dest] += 1
    rows_host = (counts - 16 * n_payloads) // (8 * width)
    assert np.array_equal(rows_host, rows_planned)
    # device half: same move set as a per-row destination vector
    for src in range(n_places):
        dest_vec = np.full(per_place, src, np.int32)
        k = 0
        for d in range(n_places):
            c = int(rows_planned[src, d])
            dest_vec[k:k + c] = d
            k += c
        dev_counts = np.asarray(spmd_counts(dest_vec, n_places))
        expect = rows_planned[src].copy()
        expect[src] = per_place - rows_planned[src].sum()  # kept rows
        assert np.array_equal(dev_counts, expect)
        # the capacity pack agrees with its own counts
        x = np.arange(per_place, dtype=np.float32)[:, None]
        _, valid, _ = _pack_by_dest(x, dest_vec, n_places, per_place)
        assert np.array_equal(np.asarray(valid).sum(axis=1), expect)
    h.finish()
    assert col.global_size() == n


# ---------------------------------------------------------------------------
# serving pipeline: depth-2 windows over live seq/KV maps
# ---------------------------------------------------------------------------
def test_serving_sim_depth2_no_lost_sequences():
    from repro.serving import ServingSim
    sim = ServingSim(n_replicas=6, speeds=(1, 1, 0.4, 1, 1, 1),
                     arrival_rate=3.0, glb_period=3, admission="count",
                     pipeline_depth=2, seed=3)
    sim.run(60)
    d = sim.driver
    assert d.lost() == 0
    # every launched window was committed and accounted (a planned
    # rebalance whose moves all clamp away launches no window)
    assert 0 < d.glb.stats.syncs_total <= d.glb.stats.rebalances
    for p in d.group.members:
        assert sorted(d.seqs.keys(p)) == sorted(d.kv.keys(p))


def test_distidmap_put_during_background_reconcile():
    """Admission-style puts racing a window's background update_dist
    never lose a key from the tracked distribution."""
    g = PlaceGroup(4)
    m = DistIdMap(g)
    for p in g.members:
        m.handle(p)
    for k in range(300):
        m.put(k % 3, k, np.float32(k))
    stop = threading.Event()
    errors = []

    def reconcile():
        try:
            while not stop.is_set():
                m.update_dist()
        except BaseException as e:   # pragma: no cover - failure path
            errors.append(e)

    t = threading.Thread(target=reconcile, daemon=True)
    t.start()
    for k in range(300, 600):
        m.put(3, k, np.float32(k))
    stop.set()
    t.join(timeout=10.0)
    assert not errors
    m.update_dist()
    dist = m.get_distribution()
    for k in range(300, 600):
        assert dist.owner_of(k) == 3


# ---------------------------------------------------------------------------
# phase-1 failure safety (ISSUE 6 satellites): no entry loss, ever
# ---------------------------------------------------------------------------
class TestPhase1FailureSafety:
    def _two_holder_col(self):
        g = PlaceGroup(3)
        col = DistArray(g, track=True)
        col.add_chunk(0, LongRange(0, 4), np.arange(8.).reshape(4, 2))
        col.add_chunk(1, LongRange(4, 8), np.arange(8., 16.).reshape(4, 2))
        return g, col

    def test_cross_holder_range_move_relocates_whole(self):
        """A range spanning two holders' chunks splits per holder
        instead of raising 'only partially held locally'."""
        g, col = self._two_holder_col()
        mm = CollectiveMoveManager(g)
        col.move_range_at_sync(LongRange(2, 6), 2, mm)
        mm.sync()
        assert col.global_size() == 8
        assert col.local_size(2) == 4
        assert [(r.start, r.end) for r in col.ranges(0)] == [(0, 2)]
        assert [(r.start, r.end) for r in col.ranges(1)] == [(6, 8)]
        got = np.concatenate([col.handle(2).chunks[r]
                              for r in col.ranges(2)])
        assert np.array_equal(got, np.arange(4., 12.).reshape(4, 2))
        # both pieces really crossed places and were accounted
        assert mm.last_counts_matrix.sum() == mm.last_payload_bytes > 0

    def test_failed_window_rolls_back_extracted_payloads(self):
        """The confirmed data-loss repro: a two-move window whose second
        move fails must re-insert what the first move extracted — the
        error still surfaces at finish(), global_size() is conserved."""
        g, col = self._two_holder_col()
        before = entry_multiset(col, 8)
        mm = CollectiveMoveManager(g)
        col.move_range_at_sync(LongRange(0, 4), 2, mm)
        # overlaps what the first move just extracted -> phase 1 raises
        col.move_range_at_sync(LongRange(2, 6), 2, mm)
        handle = mm.sync_async()
        with pytest.raises(KeyError, match="partially held"):
            handle.finish()
        assert col.global_size() == 8
        assert entry_multiset(col, 8) == before

    def test_failed_window_rolls_back_key_moves_too(self):
        g = PlaceGroup(3)
        m = DistIdMap(g)
        for k in range(6):
            m.put(k % 2, k, np.float64(k))
        col = DistArray(g, track=True)
        col.add_chunk(0, LongRange(0, 4), np.arange(8.).reshape(4, 2))
        mm = CollectiveMoveManager(g)
        m.move_at_sync(0, lambda k: 2, mm)          # extracts fine
        col.move_range_at_sync(LongRange(2, 8), 2, mm)   # then fails
        with pytest.raises(KeyError):
            mm.sync()
        assert m.global_size() == 6
        assert sorted(m.keys(0)) == [0, 2, 4]
        assert col.global_size() == 4

    def test_partial_extract_leaves_handle_untouched(self):
        """_ChunkHandle.extract validates coverage before popping: a
        partial hold raises without destroying the held intersection."""
        g = PlaceGroup(2)
        col = DistArray(g, track=False)
        col.add_chunk(0, LongRange(0, 4), np.arange(8.).reshape(4, 2))
        with pytest.raises(KeyError, match="partially held"):
            col.handle(0).extract(LongRange(2, 6))
        assert col.local_size(0) == 4
        assert [(r.start, r.end) for r in col.ranges(0)] == [(0, 4)]
