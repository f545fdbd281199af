"""Multi-process places (ISSUE 6 tentpole): ``PipeBackend`` /
``run_multiprocess`` / ``ProcessPlaceGroup`` / ``DistributedTransport``.

The heart of the suite is one real 2-process SPMD run (module-scoped —
spawn + a fresh JAX import per child is paid once): both ranks run the
same window scenario over a 4-place group with ``DistributedTransport``
and gather their final state; the tests then assert it is bit-identical
to the same scenario run in-process over ``HostTransport``.
"""
import os

import numpy as np
import pytest

from repro.core import (CollectiveMoveManager, DistArray, DistIdMap,
                        DistributedTransport, HostTransport, LocalBackend,
                        LongRange, PlaceGroup, ProcessPlaceGroup, allgather1,
                        make_transport, run_multiprocess)
from repro.core.teamed import broadcast_from

N_PLACES = 4
N_ROWS = 16
WIDTH = 3


# ---------------------------------------------------------------------------
# The SPMD scenario (module-level: spawn pickles workers by reference)
# ---------------------------------------------------------------------------
def _run_scenario(g, transport):
    """Two relocation windows over a DistArray + DistIdMap; every rank
    runs this identically (the SPMD window contract).  Handles are only
    populated for local places."""
    rows = np.arange(N_ROWS * WIDTH, dtype=np.float64).reshape(N_ROWS, WIDTH)
    col = DistArray(g, track=True)
    for p, r in enumerate(LongRange(0, N_ROWS).split(N_PLACES)):
        if g.is_local(p) and r.size:
            col.add_chunk(p, r, rows[r.start:r.end])
    kv = DistIdMap(g)
    for k in range(8):
        p = k % N_PLACES
        if g.is_local(p):
            kv.put(p, k, np.float64(k) * np.arange(3, dtype=np.float64))

    mm = CollectiveMoveManager(g, transport=transport)
    # window 1: a range spanning two holders + key moves from every place
    col.move_range_at_sync(LongRange(2, 6), 3, mm)
    for p in range(N_PLACES):
        kv.move_at_sync(p, lambda k: (int(k) * 3) % N_PLACES, mm)
    mm.sync_async((col, kv)).finish()
    # window 2: count move off the hot place + a range move back
    col.move_at_sync_count(3, 2, 0, mm)
    col.move_range_at_sync(LongRange(8, 12), 1, mm)
    mm.sync_async((col, kv)).finish()
    return col, kv, mm


def _snapshot_local(g, col, kv):
    """Byte-exact local state, keyed by place (picklable)."""
    out = {}
    for p in g.local_places():
        h = col.handle(p)
        ranges = [(r.start, r.end) for r in h.ranges()]
        keys = sorted(kv.keys(p))
        out[p] = {
            "ranges": ranges,
            "rows": b"".join(h.chunks[r].tobytes() for r in h.ranges()),
            "keys": keys,
            "vals": [np.asarray(kv.get(p, k)).tobytes() for k in keys],
        }
    return out


def _spmd_worker(backend):
    g = ProcessPlaceGroup(N_PLACES, backend)
    col, kv, mm = _run_scenario(g, DistributedTransport())
    snap: dict = {}
    for part in backend.allgather(_snapshot_local(g, col, kv)):
        snap.update(part)

    # teamed ops across processes
    vec = [float(p * 10) if g.is_local(p) else -1.0 for p in g.members]
    gathered = allgather1(g, vec)
    seen: dict = {}
    sinks = {p: (lambda v, p=p: seen.__setitem__(p, v.tolist()))
             for p in g.local_places()}
    bvalue = np.arange(4, dtype=np.float64) if g.is_local(2) else None
    broadcast_from(g, owner=2, value=bvalue, sinks=sinks)

    return {
        "rank": backend.rank,
        "local_places": g.local_places(),
        "snap": snap,
        "counts": mm.last_counts_matrix.tolist(),
        "stats_kind": mm.last_transport_stats.kind,
        "wire_exchanges": mm.last_transport_stats.exchanges,
        "dist_owner_of_9": col.get_distribution().owner_of(9),
        "kv_dist_owner_of_3": kv.get_distribution().owner_of(3),
        "allgather1": gathered.tolist(),
        "broadcast_seen": seen,
    }


@pytest.fixture(scope="module")
def two_proc():
    return run_multiprocess(_spmd_worker, 2)


@pytest.fixture(scope="module")
def reference():
    g = PlaceGroup(N_PLACES)
    col, kv, mm = _run_scenario(g, HostTransport())
    return {"snap": _snapshot_local(g, col, kv),
            "counts": mm.last_counts_matrix.tolist(),
            "dist_owner_of_9": col.get_distribution().owner_of(9),
            "kv_dist_owner_of_3": kv.get_distribution().owner_of(3)}


# ---------------------------------------------------------------------------
# The 2-process run vs the in-process HostTransport reference
# ---------------------------------------------------------------------------
class TestTwoProcessParity:
    def test_ranks_partition_the_places(self, two_proc):
        assert two_proc[0]["local_places"] == (0, 1)
        assert two_proc[1]["local_places"] == (2, 3)

    def test_final_state_bit_identical_to_host_transport(self, two_proc,
                                                         reference):
        for r in (0, 1):
            assert two_proc[r]["snap"] == reference["snap"]

    def test_counts_matrix_is_global_and_matches_host(self, two_proc,
                                                      reference):
        assert two_proc[0]["counts"] == reference["counts"]
        assert two_proc[1]["counts"] == reference["counts"]

    def test_wire_really_crossed_processes(self, two_proc):
        assert two_proc[0]["stats_kind"] == "distributed"
        assert two_proc[0]["wire_exchanges"] >= 1

    def test_tracked_distributions_reconciled_across_ranks(self, two_proc,
                                                           reference):
        for r in (0, 1):
            assert two_proc[r]["dist_owner_of_9"] \
                == reference["dist_owner_of_9"]
            assert two_proc[r]["kv_dist_owner_of_3"] \
                == reference["kv_dist_owner_of_3"]

    def test_allgather1_merges_authoritative_slots(self, two_proc):
        for r in (0, 1):
            assert two_proc[r]["allgather1"] == [0.0, 10.0, 20.0, 30.0]

    def test_broadcast_from_reaches_local_non_owner_sinks(self, two_proc):
        value = list(np.arange(4, dtype=np.float64))
        assert two_proc[0]["broadcast_seen"] == {0: value, 1: value}
        assert two_proc[1]["broadcast_seen"] == {3: value}


# ---------------------------------------------------------------------------
# Backend + launcher mechanics
# ---------------------------------------------------------------------------
def _backend_ops_worker(backend, base):
    a2a = backend.alltoall([f"{backend.rank}->{d}"
                            for d in range(backend.world_size)])
    red = backend.allreduce_sum(np.eye(2) * (backend.rank + base))
    bc = backend.broadcast("root-value" if backend.rank == 1 else None,
                           root=1)
    backend.barrier()
    return {"a2a": a2a, "red": red.tolist(), "bc": bc}


def _platform_worker(backend):
    import jax

    return os.environ.get("JAX_PLATFORMS"), jax.default_backend()


def _failing_worker(backend):
    if backend.rank == 1:
        raise RuntimeError("rank 1 exploded")
    return "ok"


class TestLauncher:
    def test_backend_collectives(self):
        out = run_multiprocess(_backend_ops_worker, 2, 1)
        assert out[0]["a2a"] == ["0->0", "1->0"]
        assert out[1]["a2a"] == ["0->1", "1->1"]
        assert out[0]["red"] == (np.eye(2) * 3).tolist()  # (1) + (2)
        assert out[0]["bc"] == "root-value"
        assert out[1]["bc"] == "root-value"

    def test_worker_exception_reraises_with_traceback(self):
        with pytest.raises(RuntimeError, match="rank 1 exploded"):
            run_multiprocess(_failing_worker, 2)

    def test_children_run_on_cpu(self, monkeypatch):
        # ranks are a host control plane: a parent holding the
        # accelerator must never race its children for it
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        out = run_multiprocess(_platform_worker, 2)
        assert out == [("cpu", "cpu"), ("cpu", "cpu")]
        assert os.environ["JAX_PLATFORMS"] == "tpu"   # parent untouched

    def test_nprocs_1_runs_inline_on_local_backend(self):
        out = run_multiprocess(_backend_ops_worker, 1, 5)
        assert out[0]["a2a"] == ["0->0"]
        assert out[0]["red"] == (np.eye(2) * 5).tolist()


# ---------------------------------------------------------------------------
# World-size-1 degradation + wiring
# ---------------------------------------------------------------------------
class TestSingleProcess:
    def test_make_transport_distributed(self):
        assert isinstance(make_transport("distributed"),
                          DistributedTransport)

    def test_world1_matches_host_semantics(self):
        g = PlaceGroup(N_PLACES)
        col, kv, mm = _run_scenario(g, DistributedTransport())
        ref = _run_scenario(PlaceGroup(N_PLACES), HostTransport())
        assert _snapshot_local(g, col, kv) \
            == _snapshot_local(ref[0].group, ref[0], ref[1])
        assert mm.last_transport_stats.kind == "distributed"
        # nothing left the process: no alltoall dispatched
        assert mm.last_transport_stats.exchanges == 0

    def test_world1_preserves_object_identity(self):
        # rank-local payloads pass through by reference (HostTransport
        # semantics) — the serving tier relies on it in-process
        g = PlaceGroup(2)
        kv = DistIdMap(g)
        marker = np.arange(5.)
        kv.put(0, 7, marker)
        mm = CollectiveMoveManager(g, transport=DistributedTransport())
        kv.move_at_sync(0, lambda k: 1, mm)
        mm.sync()
        assert kv.get(1, 7) is marker

    def test_process_place_group_defaults_to_local_backend(self):
        g = ProcessPlaceGroup(4)
        assert isinstance(g.backend, LocalBackend)
        assert not g.process_backed
        assert g.local_places() == (0, 1, 2, 3)
        assert [g.rank_of(p) for p in range(4)] == [0, 0, 0, 0]

    def test_subgroup_keeps_rank_mapping(self):
        g = ProcessPlaceGroup(4, place_ranks={0: 0, 1: 0, 2: 0, 3: 0})
        sub = g.subgroup([1, 3])
        assert sub.place_ranks == {1: 0, 3: 0}
        assert sub.backend is g.backend

    def test_serving_sim_runs_on_distributed_transport(self):
        # the serving drivers' wiring accepts the new spec end to end;
        # world-size-1 the wire is the host loopback, so the sim must
        # reproduce the host-transport run exactly
        from repro.serving import ServingSim

        def final_keys(tr):
            sim = ServingSim(n_replicas=4, arrival_rate=2.0, glb_period=3,
                             pipeline_depth=2, seed=5, transport=tr)
            sim.run(40)
            d = sim.driver
            assert d.lost() == 0
            return {p: sorted(d.seqs.keys(p)) for p in d.group.members}

        assert final_keys("distributed") == final_keys("host")

    def test_glb_config_accepts_distributed(self):
        from repro.core import GLBConfig, GlobalLoadBalancer, ListWorkload
        glb = GlobalLoadBalancer(
            4, ListWorkload([[1] * 4, [], [], []]),
            GLBConfig(transport="distributed"))
        assert isinstance(glb.transport, DistributedTransport)
