"""Chaos harness + failure detection + survivor recovery (ISSUE 9).

Three layers, cheapest first:

* plan/engine unit tests — serialization round-trips and deterministic
  fault matching, with injectable ``exit_fn``/``sleep_fn`` so nothing
  actually dies;
* in-process ``PipeBackend`` wire tests — raw ``multiprocessing.Pipe``
  pairs plus threads prove the deadline, EOF-as-death, and
  delay-ride-out behaviors without paying a spawn;
* one real 3-process failover run (module-scoped) — a chaos plan kills
  rank 2 between a window's phase-1 counts and its phase-2 delivery;
  survivors must raise :class:`PeerFailedError` (no hang), roll the
  window back, recover via :func:`recover_dead_ranks` with zero lost
  entries, and finish degraded.
"""
import os
import threading
import time

import multiprocessing as mp

import numpy as np
import pytest

from repro.core import (CollectiveMoveManager, DistArray,
                        DistributedTransport, LongRange, PeerFailedError,
                        PlaceGroup, ProcessPlaceGroup, run_multiprocess)
from repro.runtime import (ElasticWorld, HeartbeatMonitor,
                           feed_process_liveness, recover_dead_ranks)
from repro.runtime.chaos import (ChaosEngine, Fault, FaultPlan,
                                 plan_from_env)


# ---------------------------------------------------------------------------
# FaultPlan serialization
# ---------------------------------------------------------------------------
class TestFaultPlan:
    def test_json_round_trip(self):
        plan = FaultPlan(faults=(
            Fault("crash", 2, when="after", kind="allreduce_sum", nth=1),
            Fault("delay", 0, seconds=0.25, at_seq=7),
            Fault("corrupt", 1, nth=0, byte=0x0F),
            Fault("suppress_heartbeats", 3),
        ), name="trip")
        back = FaultPlan.from_json(plan.to_json())
        assert back == plan

    def test_bare_fault_list_accepted(self):
        back = FaultPlan.from_json('[{"op": "crash", "rank": 1}]')
        assert back.faults == (Fault("crash", 1),)

    def test_crash_after_convenience(self):
        plan = FaultPlan.crash_after(2, kind="allreduce_sum", nth=1)
        (f,) = plan.faults
        assert (f.op, f.rank, f.when, f.kind, f.nth) \
            == ("crash", 2, "after", "allreduce_sum", 1)

    def test_unknown_op_rejected(self):
        with pytest.raises(ValueError, match="unknown fault op"):
            Fault("explode", 0)

    def test_plan_from_env_inline_and_file(self, tmp_path):
        plan = FaultPlan.crash_after(1, at_seq=3)
        assert plan_from_env({"REPRO_CHAOS": plan.to_json()}) == plan
        path = tmp_path / "plan.json"
        path.write_text(plan.to_json())
        assert plan_from_env({"REPRO_CHAOS": f"@{path}"}) == plan
        assert plan_from_env({}) is None


# ---------------------------------------------------------------------------
# ChaosEngine matching (injected exit/sleep — nothing dies here)
# ---------------------------------------------------------------------------
def _engine(plan, rank):
    exits, sleeps = [], []
    eng = ChaosEngine(plan, rank, exit_fn=exits.append,
                      sleep_fn=sleeps.append)
    return eng, exits, sleeps


class TestChaosEngine:
    def test_crash_matches_nth_of_kind(self):
        plan = FaultPlan.crash_after(0, kind="allreduce_sum", nth=1)
        eng, exits, _ = _engine(plan, 0)
        for seq, kind in [(0, "allreduce_sum"), (1, "alltoall"),
                          (2, "allgather")]:
            eng.on_collective("before", seq, kind)
            eng.on_collective("after", seq, kind)
        assert not exits  # first allreduce_sum (nth=0) must not match
        eng.on_collective("before", 3, "allreduce_sum")
        assert not exits  # when="after": survives its own phase 1
        eng.on_collective("after", 3, "allreduce_sum")
        assert exits == [75]

    def test_wrong_rank_never_fires(self):
        plan = FaultPlan.crash_after(2, kind="barrier", nth=0)
        eng, exits, _ = _engine(plan, 0)
        for seq in range(4):
            eng.on_collective("after", seq, "barrier")
        assert not exits

    def test_delay_fires_once(self):
        plan = FaultPlan(faults=(Fault("delay", 0, seconds=0.5, at_seq=1),))
        eng, _, sleeps = _engine(plan, 0)
        for seq in range(4):
            eng.on_collective("before", seq, "alltoall")
        assert sleeps == [0.5]
        assert eng.fired_log == [("delay", 1, "alltoall")]

    def test_corrupt_flips_wire_bytes_once(self):
        plan = FaultPlan(faults=(Fault("corrupt", 0, nth=1),))
        eng, _, _ = _engine(plan, 0)
        rows = np.arange(8, dtype=np.uint8).reshape(2, 4)
        out0 = eng.corrupt_outgoing([[("g", 0, 1, rows, None)]])
        np.testing.assert_array_equal(out0[0][0][3], rows)  # nth=0 clean
        out1 = eng.corrupt_outgoing([[("g", 0, 1, rows, None)]])
        assert out1[0][0][3].reshape(-1)[0] == rows.reshape(-1)[0] ^ 0xFF
        out2 = eng.corrupt_outgoing([[("g", 0, 1, rows, None)]])
        np.testing.assert_array_equal(out2[0][0][3], rows)  # fired once

    def test_heartbeat_suppression(self):
        plan = FaultPlan(faults=(Fault("suppress_heartbeats", 1),))
        eng, _, _ = _engine(plan, 1)
        assert eng.heartbeat_suppressed()
        assert eng.heartbeat_suppressed(1)
        assert not eng.heartbeat_suppressed(0)


# ---------------------------------------------------------------------------
# PipeBackend wire behavior (in-process: raw Pipe pairs + threads)
# ---------------------------------------------------------------------------
def _pipe_backend_pair(timeout=0.4):
    from repro.core import PipeBackend
    a, b = mp.Pipe(duplex=True)
    b0 = PipeBackend(0, 2, {1: a}, collective_timeout=timeout)
    b1 = PipeBackend(1, 2, {0: b}, collective_timeout=timeout)
    return b0, b1, a, b


class TestPipeBackendDeadline:
    def test_silent_peer_trips_deadline_with_context(self):
        b0, _b1, _a, _b = _pipe_backend_pair(timeout=0.3)
        with pytest.raises(PeerFailedError) as ei:
            b0.alltoall(["x", "y"])
        e = ei.value
        assert (e.rank, e.op, e.seq) == (1, "alltoall", 0)
        assert "deadline" in str(e)
        assert b0.dead_ranks() == {1}

    def test_closed_pipe_is_peer_death(self):
        b0, _b1, _a, b = _pipe_backend_pair(timeout=5.0)
        b.close()
        with pytest.raises(PeerFailedError) as ei:
            b0.allgather("payload")
        assert ei.value.rank == 1
        assert ei.value.op == "allgather"

    def test_dead_peer_skipped_afterwards(self):
        b0, _b1, _a, _b = _pipe_backend_pair(timeout=0.2)
        with pytest.raises(PeerFailedError):
            b0.barrier()
        # collectives continue degraded: dead slots come back None
        assert b0.allgather("me") == ["me", None]
        assert b0.allreduce_sum(np.ones(2)).tolist() == [1.0, 1.0]
        with pytest.raises(ValueError, match="root rank 1 is dead"):
            b0.broadcast(None, root=1)

    def test_transient_delay_rides_out_before_deadline(self):
        b0, b1, _a, _b = _pipe_backend_pair(timeout=5.0)
        got = {}

        def late_peer():
            import time as _t
            _t.sleep(0.15)
            got["peer"] = b1.alltoall(["to0", "to1"])

        t = threading.Thread(target=late_peer, daemon=True)
        t.start()
        assert b0.alltoall(["keep", "ship"]) == ["keep", "to0"]
        t.join(timeout=5)
        assert got["peer"] == ["ship", "to1"]

    def test_resync_agrees_on_tag_and_dead_set(self):
        b0, b1, _a, _b = _pipe_backend_pair(timeout=5.0)
        # skew the tags (as two survivors that failed at different seqs)
        b0._tag, b1._tag = 4, 9
        out = {}

        def peer():
            b1.resync()
            out["tag"] = b1._tag

        t = threading.Thread(target=peer, daemon=True)
        t.start()
        b0.resync()
        t.join(timeout=5)
        assert b0._tag == out["tag"] == 10

    def test_picklable_error(self):
        import pickle
        e = PeerFailedError(2, "allgather", 7, detail="gone")
        e2 = pickle.loads(pickle.dumps(e))
        assert (e2.rank, e2.op, e2.seq, e2.detail) == (2, "allgather", 7,
                                                       "gone")


# ---------------------------------------------------------------------------
# Heartbeats fed by real liveness (+ chaos suppression)
# ---------------------------------------------------------------------------
class TestLivenessFeed:
    def test_local_group_all_beat(self):
        g = ProcessPlaceGroup(4)
        mon = HeartbeatMonitor(4, timeout_steps=1)
        for _ in range(4):
            assert feed_process_liveness(mon, g) == []
        assert mon.alive() == [0, 1, 2, 3]

    def test_suppressed_rank_looks_dead(self):
        g = ProcessPlaceGroup(4)   # LocalBackend: one rank owns all
        plan = FaultPlan(faults=(Fault("suppress_heartbeats", 0),))
        eng = ChaosEngine(plan, 0)
        mon = HeartbeatMonitor(4, timeout_steps=1)
        newly: list = []
        for _ in range(3):
            newly += feed_process_liveness(mon, g, chaos=eng)
        assert sorted(newly) == [0, 1, 2, 3]
        assert mon.alive() == []


# ---------------------------------------------------------------------------
# ElasticWorld.resize through the relocation engine
# ---------------------------------------------------------------------------
class TestResizeThroughEngine:
    def test_resize_preserves_rows_by_global_index(self):
        g = PlaceGroup(4)
        col = DistArray(g, track=True)
        rows = np.arange(40, dtype=np.float64)[:, None]
        for p, r in enumerate(LongRange(0, 40).split(4)):
            col.add_chunk(p, r, rows[r.start:r.end])
        world = ElasticWorld(g)
        world.resize(6, [col])
        world.resize(2, [col])
        assert col.global_size() == 40
        assert col.get_distribution().loads(2).tolist() == [20, 20]
        # entry i still holds value i: the re-partition relocated
        # entries, it did not renumber them
        for p in world.group.members:
            h = col.handle(p)
            for r in h.ranges():
                np.testing.assert_array_equal(
                    h.chunks[r], rows[r.start:r.end])


# ---------------------------------------------------------------------------
# The 3-process failover run (module-scoped: one spawn for all asserts)
# ---------------------------------------------------------------------------
FO_PLACES = 6
FO_ROWS = 24
FO_WIDTH = 2


def _replicated_array(g):
    """SPMD-deterministic init: every rank materializes every place's
    chunk — warm replicas, the redundancy contract recovery consumes
    (a dead place can only be re-homed from entries survivors hold)."""
    rows = np.arange(FO_ROWS * FO_WIDTH,
                     dtype=np.float64).reshape(FO_ROWS, FO_WIDTH)
    col = DistArray(g, track=True)
    for p, r in enumerate(LongRange(0, FO_ROWS).split(FO_PLACES)):
        col.add_chunk(p, r, rows[r.start:r.end])
    return col


def _failover_worker(backend):
    g = ProcessPlaceGroup(FO_PLACES, backend)
    col = _replicated_array(g)
    transport = DistributedTransport()
    mm = CollectiveMoveManager(g, transport=transport)
    # the first cross-rank window: places 0 (rank 0) -> 2 (rank 1).
    # The chaos plan kills rank 2 right after the phase-1 counts
    # allreduce completes, so survivors hit the death in phase 2.
    mm.register_range_move(col, LongRange(0, 4), 2)
    err = None
    t0 = time.perf_counter()
    try:
        mm.sync()
    except PeerFailedError as e:
        err = {"rank": e.rank, "op": e.op, "seq": e.seq,
               "detail": str(e)}
    if err is None:
        return {"failed": False}
    detect_s = time.perf_counter() - t0
    mm.abort_inflight()

    t0 = time.perf_counter()
    new_g, stats = recover_dead_ranks(g, [col], transport=transport)
    recovery_s = time.perf_counter() - t0

    # finish degraded: another window over the survivors
    mm2 = CollectiveMoveManager(new_g, transport=transport)
    mm2.register_range_move(col, LongRange(4, 6), 3)
    mm2.sync()

    local = int(sum(col.local_size(p) for p in new_g.local_places()))
    total = int(backend.allreduce_sum(np.int64(local)))
    return {
        "failed": True,
        "err": err,
        "dead_ranks": stats["dead_ranks"],
        "dead_places": stats["dead_places"],
        "adopters": stats["adopters"],
        "rehomed": stats["rehomed"],
        "unrecovered": stats["unrecovered"],
        "total_after": total,
        "detect_s": detect_s,
        "recovery_s": recovery_s,
        "live_places": new_g.local_places(),
        "members": new_g.members,
    }


@pytest.fixture(scope="module")
def failover():
    plan = FaultPlan.crash_after(2, kind="allreduce_sum", nth=0)
    return run_multiprocess(_failover_worker, 3, chaos=plan,
                            collective_timeout=15.0, recover=True,
                            timeout=150.0)


class TestThreeProcessFailover:
    def test_dead_rank_slot_is_none_survivors_report(self, failover):
        assert failover[2] is None
        assert failover[0]["failed"] and failover[1]["failed"]

    def test_error_names_rank_op_seq(self, failover):
        for r in (0, 1):
            err = failover[r]["err"]
            assert err["rank"] == 2
            assert err["op"]
            assert isinstance(err["seq"], int)
            assert "rank 2" in err["detail"]

    def test_survivors_agree_on_dead_set(self, failover):
        for r in (0, 1):
            assert failover[r]["dead_ranks"] == (2,)
            assert failover[r]["dead_places"] == (4, 5)

    def test_every_dead_entry_rehomed_zero_lost(self, failover):
        for r in (0, 1):
            assert failover[r]["unrecovered"] == ()
            assert sum(failover[r]["rehomed"].values()) == 2 * (
                FO_ROWS // FO_PLACES)
            # global entry count conserved across the crash + recovery
            assert failover[r]["total_after"] == FO_ROWS

    def test_survivor_group_shrank_and_finished_degraded(self, failover):
        assert failover[0]["members"] == (0, 1, 2, 3)
        assert failover[0]["live_places"] == (0, 1)
        assert failover[1]["live_places"] == (2, 3)

    def test_recovery_bounded_well_under_deadline(self, failover):
        # the dead peer's closed pipe is detected at once, not after the
        # 15 s collective deadline, and recovery is collectives + local
        # inserts: crash to recovered stays far under the deadline
        for r in (0, 1):
            assert failover[r]["detect_s"] + failover[r]["recovery_s"] \
                < 10.0


# ---------------------------------------------------------------------------
# Corrupt fault reaches the wire (2-process)
# ---------------------------------------------------------------------------
def _corrupt_worker(backend):
    g = ProcessPlaceGroup(4, backend)
    col = _replicated_array_4(g)
    mm = CollectiveMoveManager(g, transport=DistributedTransport())
    mm.register_range_move(col, LongRange(0, 2), 2)  # rank 0 -> rank 1
    mm.sync()
    if not g.is_local(2):
        return None
    h = col.handle(2)
    return b"".join(h.chunks[r].tobytes() for r in sorted(
        h.ranges(), key=lambda r: r.start))


def _replicated_array_4(g):
    rows = np.arange(16, dtype=np.float64).reshape(8, 2)
    col = DistArray(g, track=False)
    for p, r in enumerate(LongRange(0, 8).split(4)):
        col.add_chunk(p, r, rows[r.start:r.end])
    return col


class TestCorruptFault:
    def test_corrupt_plan_alters_delivered_bytes(self):
        clean = run_multiprocess(_corrupt_worker, 2)
        plan = FaultPlan(faults=(Fault("corrupt", 0, nth=0),))
        dirty = run_multiprocess(_corrupt_worker, 2, chaos=plan)
        assert clean[1] is not None and dirty[1] is not None
        assert clean[1] != dirty[1]


# ---------------------------------------------------------------------------
# Launcher: recovery mode + zombie reaping
# ---------------------------------------------------------------------------
def _hard_exit_worker(backend):
    if backend.rank == 1:
        os._exit(75)
    return "ok"


class TestLauncherRecovery:
    def test_death_without_recover_reports_exit_codes(self):
        with pytest.raises(RuntimeError) as ei:
            run_multiprocess(_hard_exit_worker, 2, timeout=60.0)
        msg = str(ei.value)
        assert "rank 1" in msg
        assert "per-rank exit codes" in msg
        assert "75" in msg

    def test_recover_tolerates_death_with_survivor(self):
        out = run_multiprocess(_hard_exit_worker, 2, timeout=60.0,
                               recover=True)
        assert out == ["ok", None]
