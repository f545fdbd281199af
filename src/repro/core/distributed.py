"""Multi-process places: the relocation data plane leaves the process.

Every other module in ``core/`` models places inside one OS process.
This module supplies the three pieces that let the *same* APIs span
processes, the way BCL hides MPI/SHMEM/GASNet-EX behind one
container-facing backend seam:

* **Process backends** — :class:`PipeBackend` gives a real N-process
  exchange over ``multiprocessing.connection`` pipes (runs anywhere,
  including CPU-only CI); :class:`LocalBackend` is the world-size-1
  degenerate case so ``transport="distributed"`` also works in-process.
  Every collective carries a sequence tag, so a rank that falls out of
  program order fails loudly instead of decoding another window's
  bytes.

* **run_multiprocess** — a ``spawn``-based launcher: one worker
  function runs SPMD on every rank, pre-wired pipes form the full mesh,
  per-rank results (or tracebacks) come back to the caller.

* **ProcessPlaceGroup / DistributedTransport** — a ``PlaceGroup``
  whose places are block-partitioned across ranks, and the third
  :class:`~repro.core.transport.RelocationTransport`: phase-1 counts
  ride the backend as an allreduce, payload rows are encoded by the
  *same* PR-5 row codecs (``encode_rows``/``decode_rows``) and cross
  the process boundary through one alltoall per window.  Where a
  multi-controller ``jax.distributed`` runtime is initialized, the row
  payload can instead ride a device-mesh ``all_to_all``
  (``device_wire="auto"``); the serialized pipe wire is the
  CPU-CI-provable fallback and the default everywhere else.

SPMD contract (mirrors the paper's teamed semantics): every rank runs
the same program — creates collections in the same order (global ids
are the wire addresses), registers the same *range* moves on every
rank (each rank relocates the pieces it holds; coverage is validated
globally), and may register src-explicit moves anywhere (only the rank
owning ``src`` extracts).  ``sync()`` is collective.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
import traceback
from typing import Any, Callable, Sequence

import numpy as np

from ..analysis import sanitizer as _san
from . import telemetry
from .collections import PlaceGroup, _host_bytes, lookup_collection
from .transport import TransportStats, _account_exchange

__all__ = [
    "LocalBackend",
    "PeerFailedError",
    "PipeBackend",
    "run_multiprocess",
    "current_backend",
    "ProcessPlaceGroup",
    "DistributedTransport",
]

# control-message kinds on the pipe wire (never collide with collective
# kinds, which are plain identifiers)
_ABORT_KIND = "__abort__"
_RESYNC_KIND = "__resync__"

# per-collective deadline: how long a rank waits for any single peer
# message before declaring the peer failed.  Well under the launcher's
# 180 s timeout so survivors always report before the parent gives up.
_DEFAULT_COLLECTIVE_TIMEOUT = 30.0


def _collective_timeout_default() -> float:
    try:
        return float(os.environ.get("REPRO_COLLECTIVE_TIMEOUT",
                                    _DEFAULT_COLLECTIVE_TIMEOUT))
    except ValueError:
        return _DEFAULT_COLLECTIVE_TIMEOUT


class PeerFailedError(RuntimeError):
    """A peer rank died (closed pipe) or blew the collective deadline.

    Carries the failure coordinates — ``rank`` (the dead peer), ``op``
    (the collective kind this rank was running), ``seq`` (its sequence
    tag) — and renders them with the sanitizer digest-ring tail, so a
    mid-window death reads as *which* rank failed *where* instead of a
    180 s launcher timeout.  Survivors recover by rolling back the
    in-flight window (automatic), then calling
    :func:`repro.runtime.fault_tolerance.recover_dead_ranks` — which is
    collective: every survivor must run it."""

    def __init__(self, rank: int, op: str, seq: int, detail: str = ""):
        self.rank = int(rank)
        self.op = op
        self.seq = int(seq)
        self.detail = detail
        msg = (f"peer rank {rank} failed during collective #{seq} ({op})"
               + (f": {detail}" if detail else "")
               + f"; recent collectives: {_san.digest_ring().describe()}")
        super().__init__(msg)

    def __reduce__(self):
        return (type(self), (self.rank, self.op, self.seq, self.detail))


# ---------------------------------------------------------------------------
# Process backends
# ---------------------------------------------------------------------------
class LocalBackend:
    """World-size-1 backend: every collective is the identity.  Lets
    ``transport="distributed"`` (and every process-aware code path) run
    unchanged inside a single process."""

    rank = 0
    world_size = 1
    chaos = None

    def alltoall(self, objs: Sequence[Any]) -> list:
        if len(objs) != 1:
            raise ValueError("LocalBackend alltoall expects 1 entry")
        return list(objs)

    def allgather(self, obj: Any) -> list:
        return [obj]

    def allreduce_sum(self, arr) -> np.ndarray:
        return np.asarray(arr)

    def broadcast(self, obj: Any, root: int = 0) -> Any:
        return obj

    def barrier(self) -> None:
        pass

    def dead_ranks(self) -> frozenset:
        return frozenset()

    def live_ranks(self) -> tuple:
        return (0,)

    def resync(self) -> None:
        pass


class PipeBackend:
    """Full-mesh ``multiprocessing.connection`` backend.

    One duplex pipe per rank pair; each pairwise handshake is ordered
    (the lower rank sends first, the higher recvs first) so a large
    message can never deadlock two ranks that both block in ``send``.
    Every message carries ``(tag, kind, payload)`` where ``tag`` is
    this backend's collective sequence number and ``kind`` names the
    collective that issued it — ranks that drift out of program order
    (two threads racing collectives, a skipped sync) raise with *what*
    each rank was running plus this rank's recent-collective history
    (the sanitizer's digest ring), instead of silently decoding the
    wrong window.

    Collectives are deadline-aware: every receive polls with bounded
    backoff up to ``collective_timeout`` seconds (default 30, or
    ``REPRO_COLLECTIVE_TIMEOUT``), so transient peer slowness rides out
    for free while a closed pipe (peer process death) or a blown
    deadline raises :class:`PeerFailedError` naming the dead rank, the
    op kind and the seq tag — no survivor ever blocks to the launcher
    timeout.  A rank that detects a death mid-collective aborts the
    collective on every live peer (an out-of-band abort token), so the
    failure surfaces on all survivors within one deadline.  After
    catching it, survivors run :meth:`resync` (collective over the live
    mesh) to flush stale messages and agree on the dead set + the next
    sequence tag; collectives thereafter skip dead peers (their slots
    come back ``None``) and the program continues degraded.
    """

    def __init__(self, rank: int, world_size: int, conns: dict, *,
                 collective_timeout: float | None = None, chaos=None):
        self.rank = int(rank)
        self.world_size = int(world_size)
        self._conns = conns              # peer rank -> Connection
        self._tag = 0
        self._lock = threading.Lock()    # collectives serialize in-process
        self.collective_timeout = (_collective_timeout_default()
                                   if collective_timeout is None
                                   else float(collective_timeout))
        self.chaos = chaos               # ChaosEngine or None
        self._dead: set[int] = set()
        # resync tokens that arrived early (a peer entered recovery
        # while we were still swapping): consumed by resync()
        self._stash: dict[int, Any] = {}

    # -- liveness ---------------------------------------------------------
    def dead_ranks(self) -> frozenset:
        return frozenset(self._dead)

    def live_ranks(self) -> tuple:
        return tuple(r for r in range(self.world_size)
                     if r not in self._dead)

    def _mark_dead(self, peer: int, op: str, seq: int) -> None:
        if peer in self._dead:
            return
        self._dead.add(peer)
        if telemetry.enabled():
            telemetry.inc("fault.peer_failed")
            telemetry.event("fault.peer_failed", peer=int(peer), op=op,
                            seq=int(seq), rank=self.rank)

    # -- deadline-aware wire ----------------------------------------------
    def _send(self, peer: int, msg: tuple, op: str, seq: int) -> None:
        try:
            self._conns[peer].send(msg)
        except (BrokenPipeError, OSError):
            self._mark_dead(peer, op, seq)
            raise PeerFailedError(peer, op, seq,
                                  detail="pipe closed while sending "
                                         "(peer process died)")

    def _recv(self, peer: int, op: str, seq: int) -> tuple:
        """One deadline-bounded receive: poll with exponential backoff
        until a message lands; EOF/closed pipe is peer death, deadline
        expiry is a suspected death (hang or drift) — both raise
        :class:`PeerFailedError` instead of blocking forever."""
        c = self._conns[peer]
        deadline = time.monotonic() + self.collective_timeout
        wait = 0.0005
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self._mark_dead(peer, op, seq)
                raise PeerFailedError(
                    peer, op, seq,
                    detail=f"no message within the "
                           f"{self.collective_timeout:.1f}s collective "
                           "deadline (peer hung, died, or fell out of "
                           "program order)")
            try:
                if c.poll(min(wait, remaining)):
                    return c.recv()
            except (EOFError, OSError):
                self._mark_dead(peer, op, seq)
                raise PeerFailedError(peer, op, seq,
                                      detail="pipe closed (peer process "
                                             "died)")
            wait = min(wait * 2, 0.05)   # bounded retry backoff

    def _abort_peers(self, tag: int, kind: str) -> None:
        """Best-effort: tell every live peer this collective is aborted
        (they may be blocked waiting for us or for the dead rank) so the
        failure surfaces everywhere within one deadline, not N."""
        token = (tag, _ABORT_KIND, tuple(sorted(self._dead)))
        for peer in range(self.world_size):
            if peer == self.rank or peer in self._dead:
                continue
            try:
                self._conns[peer].send(token)
            except (BrokenPipeError, OSError):
                self._mark_dead(peer, kind, tag)

    # -- pairwise ordered exchange ---------------------------------------
    def _swap(self, peer: int, obj: Any, tag: int,
              kind: str = "alltoall") -> Any:
        if self.rank < peer:
            self._send(peer, (tag, kind, obj), kind, tag)
            rtag, rkind, got = self._recv(peer, kind, tag)
        else:
            rtag, rkind, got = self._recv(peer, kind, tag)
            self._send(peer, (tag, kind, obj), kind, tag)
        if rkind == _ABORT_KIND:
            # the peer detected a death mid-collective and aborted:
            # adopt its dead set and surface the same failure here
            self._dead.update(got)
            dead = min(got) if got else peer
            raise PeerFailedError(
                dead, kind, tag,
                detail=f"collective aborted by rank {peer} after it "
                       f"detected dead rank(s) {sorted(got) or [peer]}")
        if rkind == _RESYNC_KIND:
            # the peer already entered recovery; keep its token for our
            # own resync() and report the failure it is recovering from
            self._stash[peer] = got
            dead_set = got[0]
            self._dead.update(dead_set)
            dead = min(dead_set) if dead_set else peer
            raise PeerFailedError(
                dead, kind, tag,
                detail=f"rank {peer} is resyncing after dead rank(s) "
                       f"{sorted(dead_set) or [peer]}")
        if rtag != tag or rkind != kind:
            # kind mismatch at an equal tag is the nastier drift: the
            # old (tag, payload) wire silently decoded the wrong
            # collective's bytes (e.g. one rank's barrier swapping with
            # another's allgather)
            raise RuntimeError(
                f"rank {self.rank} got collective #{rtag} ({rkind}) "
                f"from rank {peer} while running #{tag} ({kind}) — "
                "ranks out of program order (collectives must be "
                "issued identically on every rank); recent collectives "
                f"on rank {self.rank}: "
                f"{_san.digest_ring().describe()}")
        return got

    def alltoall(self, objs: Sequence[Any], *,
                 kind: str = "alltoall") -> list:
        if len(objs) != self.world_size:
            raise ValueError(
                f"alltoall needs {self.world_size} entries, got {len(objs)}")
        with self._lock:
            tag = self._tag
            self._tag += 1
            # always feed the diagnostic ring (one deque append): a tag
            # mismatch names what *both* ranks were doing even when the
            # run was not sanitized
            _san.digest_ring().record(tag, kind)
            if self.chaos is not None:
                self.chaos.on_collective("before", tag, kind)
            out = [None] * self.world_size
            out[self.rank] = objs[self.rank]
            try:
                for peer in range(self.world_size):
                    if peer == self.rank or peer in self._dead:
                        continue
                    out[peer] = self._swap(peer, objs[peer], tag, kind)
            except PeerFailedError:
                self._abort_peers(tag, kind)
                raise
            if self.chaos is not None:
                self.chaos.on_collective("after", tag, kind)
            return out

    def allgather(self, obj: Any) -> list:
        """Gathered list in rank order; dead ranks' slots are ``None``."""
        return self.alltoall([obj] * self.world_size, kind="allgather")

    def allreduce_sum(self, arr) -> np.ndarray:
        arr = np.asarray(arr)
        out = np.zeros_like(arr)
        for part in self.alltoall([arr] * self.world_size,
                                  kind="allreduce_sum"):
            if part is not None:    # dead ranks contribute zero
                out = out + np.asarray(part)
        return out

    def broadcast(self, obj: Any, root: int = 0) -> Any:
        # ride the same tagged alltoall so broadcasts stay in program
        # order with every other collective (N small control messages)
        if root in self._dead:
            raise ValueError(f"broadcast root rank {root} is dead")
        got = self.alltoall(
            [obj if self.rank == root else None] * self.world_size,
            kind="broadcast")
        return got[root]

    def barrier(self) -> None:
        self.alltoall([None] * self.world_size, kind="barrier")

    # -- post-failure resynchronization -----------------------------------
    def _drain_until_resync(self, peer: int):
        """Discard the peer's stale in-flight messages (aborted-swap
        payloads, abort tokens) until its resync token arrives — FIFO
        pipes guarantee everything the peer sent before entering
        resync() is consumed here.  Returns the token payload, or
        ``None`` when the peer itself died."""
        c = self._conns[peer]
        deadline = time.monotonic() + self.collective_timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self._mark_dead(peer, _RESYNC_KIND, self._tag)
                return None
            try:
                if not c.poll(min(0.01, remaining)):
                    continue
                _rtag, rkind, payload = c.recv()
            except (EOFError, OSError):
                self._mark_dead(peer, _RESYNC_KIND, self._tag)
                return None
            if rkind == _RESYNC_KIND:
                return payload
            if rkind == _ABORT_KIND:
                self._dead.update(payload)
            # anything else is a stale swap payload of an aborted
            # collective: drop it

    def resync(self) -> None:
        """Collective over the survivors after a
        :class:`PeerFailedError`: flush every stale in-flight message,
        agree on the union dead set, and re-align the collective
        sequence tag (survivors may have failed at different seqs when
        the dead rank's last sends were partially buffered).  Every
        survivor must call this before issuing further collectives —
        :func:`repro.runtime.fault_tolerance.recover_dead_ranks` does.

        Best-effort under cascading failures: a rank that dies *during*
        resync is added to the dead set; if survivors then disagree on
        the tag, the next collective raises and recovery re-enters."""
        with self._lock:
            token = (tuple(sorted(self._dead)), self._tag)
            for peer in range(self.world_size):
                if peer == self.rank or peer in self._dead:
                    continue
                try:
                    self._conns[peer].send(
                        (self._tag, _RESYNC_KIND, token))
                except (BrokenPipeError, OSError):
                    self._mark_dead(peer, _RESYNC_KIND, self._tag)
            tags = [self._tag]
            for peer in range(self.world_size):
                if peer == self.rank or peer in self._dead:
                    continue
                payload = self._stash.pop(peer, None)
                if payload is None:
                    payload = self._drain_until_resync(peer)
                if payload is None:
                    continue    # peer died during resync
                dead_set, ptag = payload
                self._dead.update(dead_set)
                tags.append(int(ptag))
            self._stash.clear()
            self._tag = max(tags) + 1
            if telemetry.enabled():
                telemetry.event("recover.resync", rank=self.rank,
                                dead=tuple(sorted(self._dead)),
                                tag=self._tag)


_CURRENT_BACKEND: list = [None]


def current_backend():
    """The backend this process was launched with (see
    :func:`run_multiprocess`), or ``None`` outside a launched worker."""
    return _CURRENT_BACKEND[0]


def _set_current_backend(backend) -> None:
    _CURRENT_BACKEND[0] = backend


# ---------------------------------------------------------------------------
# The launcher
# ---------------------------------------------------------------------------
def _load_chaos_engine(rank: int, chaos_json: str | None):
    """Build this rank's ChaosEngine from the launcher-shipped plan (or
    the REPRO_CHAOS env var) and install it process-wide.  Lazy import:
    ``repro.runtime`` depends on ``repro.core``, never the reverse at
    module scope."""
    if not chaos_json and not os.environ.get("REPRO_CHAOS"):
        return None
    from ..runtime import chaos as _chaos

    plan = (_chaos.FaultPlan.from_json(chaos_json) if chaos_json
            else _chaos.plan_from_env())
    if plan is None or not plan.faults:
        return None
    engine = _chaos.ChaosEngine(plan, rank)
    _chaos.install(engine)
    return engine


def _worker_main(fn, rank, world_size, conns, result_conn, args, kwargs,
                 collect_trace=False, sanitize=False, chaos_json=None,
                 collective_timeout=None):
    """Spawn entry point (module-level so it pickles under spawn)."""
    engine = _load_chaos_engine(rank, chaos_json)
    backend = PipeBackend(rank, world_size, conns,
                          collective_timeout=collective_timeout,
                          chaos=engine)
    _set_current_backend(backend)
    trace = None
    try:
        if sanitize:
            # full data-plane sanitizer in every rank (forces telemetry
            # on — the span stream is its event source)
            _san.enable(rank=rank)
        if collect_trace:
            # every record this rank emits is pid-tagged with its rank;
            # the shutdown allgather below then hands every rank the
            # same merged cross-rank timeline
            telemetry.enable(rank=rank)
        result = fn(backend, *args, **kwargs)
        if collect_trace:
            try:
                trace = telemetry.allgather_spans(backend)
            except Exception:
                # a peer died mid-merge (its failure is reported on its
                # own result pipe) — degrade to this rank's records
                trace = telemetry.tracer().records()
        payload = ("ok", result, trace)
    except BaseException:
        payload = ("err", traceback.format_exc(), None)
    try:
        result_conn.send(payload)
    except Exception:
        # unpicklable result: report that instead of hanging the parent
        result_conn.send(("err", f"rank {rank}: result not picklable",
                          None))
    finally:
        result_conn.close()


def run_multiprocess(fn: Callable, nprocs: int, *args,
                     timeout: float = 180.0,
                     collect_trace: bool = False,
                     sanitize: bool = False,
                     chaos=None,
                     collective_timeout: float | None = None,
                     recover: bool = False, **kwargs):
    """Run ``fn(backend, *args, **kwargs)`` SPMD on ``nprocs`` fresh OS
    processes (``spawn`` — no inherited JAX state) wired into a full
    pipe mesh; returns the per-rank results in rank order.

    The ranks are a host control plane: every child starts with
    ``JAX_PLATFORMS=cpu``, so a parent that holds the accelerator never
    races its children for it (an accelerator belongs to one process).

    ``fn`` must be a module-level function (spawn pickles it by
    reference) and arguments/results must be picklable.  From a script,
    call this under ``if __name__ == "__main__":`` — spawn re-imports
    the main module in every child, the standard multiprocessing
    contract.  Any rank's exception re-raises here with its traceback;
    ``nprocs == 1`` runs ``fn`` inline on a :class:`LocalBackend` (no
    spawn, no pickling).

    ``collect_trace=True`` enables telemetry in every worker (rank
    tags each record's ``pid``), merges all ranks' tracer buffers over
    the backend allgather at shutdown, and returns ``(results,
    timeline)`` — one rank-tagged list of trace-event records ready for
    :func:`repro.core.telemetry.chrome_trace`.

    ``sanitize=True`` enables the full relocation sanitizer
    (:mod:`repro.analysis.sanitizer` — race detector, SPMD contract
    checker, transport invariants) in every worker, same as setting
    ``REPRO_SANITIZE=1`` in their environment.

    ``chaos`` ships a :class:`repro.runtime.chaos.FaultPlan` (or its
    JSON) to every worker — deterministic fault injection at the
    backend/transport seams; the ``REPRO_CHAOS`` env var is the
    equivalent out-of-band channel.  ``collective_timeout`` overrides
    each worker's per-collective deadline (``REPRO_COLLECTIVE_TIMEOUT``,
    default 30 s).

    ``recover=True`` is the supervised recovery mode: a rank that dies
    without reporting (crashed, killed, or chaos-crashed) no longer
    fails the whole run as long as at least one survivor returns a
    result — dead ranks' slots come back ``None``.  Workers are
    expected to handle :class:`PeerFailedError` by running
    :func:`repro.runtime.fault_tolerance.recover_dead_ranks` and
    continuing degraded; a survivor that *raises* still fails the run
    with its traceback."""
    if nprocs < 1:
        raise ValueError(f"nprocs must be >= 1, got {nprocs}")
    chaos_json = None
    if chaos is not None:
        chaos_json = chaos.to_json() if hasattr(chaos, "to_json") else chaos
    if nprocs == 1:
        backend = LocalBackend()
        prev = current_backend()
        _set_current_backend(backend)
        was_enabled = telemetry.enabled()
        was_sanitizing = _san._ACTIVE
        engine = _load_chaos_engine(0, chaos_json)
        backend.chaos = engine
        if sanitize and not was_sanitizing:
            _san.enable(rank=0)
        if collect_trace and not telemetry.enabled():
            telemetry.enable(rank=0)
        try:
            results = [fn(backend, *args, **kwargs)]
            if collect_trace:
                return results, telemetry.allgather_spans(backend)
            return results
        finally:
            if sanitize and not was_sanitizing:
                _san.disable()
            if (collect_trace or sanitize) and not was_enabled:
                telemetry.disable()
            if engine is not None:
                from ..runtime import chaos as _chaos
                _chaos.clear()
            _set_current_backend(prev)

    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    # full mesh: one duplex pipe per rank pair
    ends: dict[int, dict[int, Any]] = {r: {} for r in range(nprocs)}
    for i in range(nprocs):
        for j in range(i + 1, nprocs):
            ci, cj = ctx.Pipe(duplex=True)
            ends[i][j] = ci
            ends[j][i] = cj
    procs, result_conns = [], []
    # spawn copies os.environ into each child at start(), and JAX reads
    # JAX_PLATFORMS when the child imports it
    prev_platforms = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        for r in range(nprocs):
            parent_end, child_end = ctx.Pipe(duplex=False)
            p = ctx.Process(target=_worker_main,
                            args=(fn, r, nprocs, ends[r], child_end,
                                  args, kwargs, collect_trace, sanitize,
                                  chaos_json, collective_timeout),
                            daemon=True)
            p.start()
            child_end.close()
            for c in ends[r].values():
                c.close()   # parent's copies; the children own them now
            procs.append(p)
            result_conns.append(parent_end)
    finally:
        if prev_platforms is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev_platforms

    results: list = [None] * nprocs
    # survivor tracebacks always fail the run; deaths (no result, EOF)
    # are tolerated in recovery mode when any rank reported back
    fatal: list[str] = []
    deaths: list[str] = []
    ok_count = 0
    timeline: list | None = None
    exit_codes: dict[int, Any] = {}
    try:
        for r, conn in enumerate(result_conns):
            if not conn.poll(timeout):
                deaths.append(f"rank {r}: no result within {timeout}s")
                continue
            try:
                status, value, trace = conn.recv()
            except EOFError:
                deaths.append(
                    f"rank {r}: died without reporting; if launching "
                    f"from a script, run_multiprocess must be called "
                    f"under `if __name__ == \"__main__\":` (spawn "
                    f"re-imports the main module in every child)")
                continue
            if status == "ok":
                ok_count += 1
                results[r] = value
                # the shutdown allgather handed every rank the same
                # merged timeline; keep the first (longest, if a peer
                # degraded to local records mid-failure)
                if trace is not None and (timeline is None
                                          or len(trace) > len(timeline)):
                    timeline = trace
            else:
                fatal.append(f"rank {r} failed:\n{value}")
    finally:
        for r, p in enumerate(procs):
            # escalating reap: join → terminate → kill, so a hung or
            # crashed worker can never linger as a zombie past the call
            p.join(timeout=10.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
            if p.is_alive():
                p.kill()
                p.join()
            exit_codes[r] = p.exitcode
        for conn in result_conns:
            conn.close()
    if fatal or (deaths and not (recover and ok_count > 0)):
        codes = ", ".join(f"rank {r}: {c}"
                          for r, c in sorted(exit_codes.items()))
        raise RuntimeError(
            "run_multiprocess: " + "\n".join(fatal + deaths)
            + f"\nper-rank exit codes: {{{codes}}}")
    if collect_trace:
        return results, (timeline or [])
    return results


# ---------------------------------------------------------------------------
# Process-backed place groups
# ---------------------------------------------------------------------------
class ProcessPlaceGroup(PlaceGroup):
    """A :class:`PlaceGroup` whose places are partitioned across OS
    processes: contiguous blocks of members per rank (rank 0 gets the
    first ``ceil(n/W)`` places, and so on), or an explicit
    ``place_ranks`` mapping.  The teamed-op API is unchanged — the
    relocation engine and ``teamed.py`` consult :meth:`rank_of` /
    :meth:`local_places` and route cross-rank traffic through the
    process backend."""

    def __init__(self, n_places: int, backend=None, *,
                 place_ranks: dict[int, int] | None = None,
                 mesh=None, axis: str | None = None,
                 members: Sequence[int] | None = None):
        super().__init__(n_places, mesh=mesh, axis=axis, members=members)
        if backend is None:
            backend = current_backend() or LocalBackend()
        self.backend = backend
        W = backend.world_size
        if place_ranks is None:
            base, rem = divmod(self.n_places, W)
            place_ranks = {}
            i = 0
            for r in range(W):
                take = base + (1 if r < rem else 0)
                for p in self.members[i:i + take]:
                    place_ranks[p] = r
                i += take
        self.place_ranks = {int(p): int(r) for p, r in place_ranks.items()}
        for p in self.members:
            r = self.place_ranks.get(p)
            if r is None or not (0 <= r < W):
                raise ValueError(f"place {p} has no valid rank (world {W})")

    @property
    def process_backed(self) -> bool:  # type: ignore[override]
        return self.backend.world_size > 1

    def rank_of(self, place: int) -> int:
        return self.place_ranks[place]

    def is_local(self, place: int) -> bool:
        return self.place_ranks[place] == self.backend.rank

    def local_places(self) -> tuple:
        me = self.backend.rank
        return tuple(p for p in self.members if self.place_ranks[p] == me)

    def exchange_counts(self, counts: np.ndarray) -> np.ndarray:
        if not self.process_backed:
            return counts
        return self.backend.allreduce_sum(counts)

    def exchange_range_claims(self, claims: Sequence[int]) -> list[int]:
        claims = [int(c) for c in claims]
        if not self.process_backed:
            return claims
        gathered = [c for c in self.backend.allgather(claims)
                    if c is not None]   # dead ranks contribute nothing
        if len({len(c) for c in gathered}) > 1:
            raise RuntimeError(
                "range moves must be registered on every rank, in the "
                "same order (the SPMD window contract): got per-rank "
                f"range-move counts {[len(c) for c in gathered]}")
        return [int(sum(c[i] for c in gathered))
                for i in range(len(claims))]

    def subgroup(self, members: Sequence[int]) -> "ProcessPlaceGroup":
        members = tuple(members)
        full = members == self.members
        return ProcessPlaceGroup(
            len(members), self.backend,
            place_ranks={p: self.place_ranks[p] for p in members},
            mesh=self.mesh if full else None,
            axis=self.axis if full else None,
            members=members)

    def __repr__(self) -> str:
        return (f"ProcessPlaceGroup({list(self.members)}, "
                f"rank={self.backend.rank}/{self.backend.world_size})")


# ---------------------------------------------------------------------------
# The transport
# ---------------------------------------------------------------------------
class DistributedTransport:
    """The §5.3 Alltoallv across OS processes.

    Payload rows are encoded by the owning collection's row codec — the
    exact wire format :class:`~repro.core.transport.DeviceTransport`
    ships on-device — then cross the process boundary through one
    backend ``alltoall`` per window.  Wire entries are addressed by
    collection ``global_id`` (equal across ranks for SPMD programs);
    rank-local payloads (including self-moves) pass through by
    reference, exactly like :class:`HostTransport`, so a world-size-1
    run degrades to the host loopback.

    ``device_wire="auto"`` (default): when a multi-controller
    ``jax.distributed`` runtime is initialized and one addressable
    device per process is available, chunk-matrix rows ride a
    process-spanning device-mesh ``all_to_all`` instead of the pickled
    pipe — manifests and control stay on the backend.  CPU-only CI
    never takes this path; it is exercised only under a real
    ``jax.distributed.initialize`` launch.  ``device_wire="off"``
    forces the serialized wire.
    """

    device_plane = False

    def __init__(self, backend=None, *, device_wire: str = "auto"):
        if device_wire not in ("auto", "off"):
            raise ValueError(f"device_wire must be 'auto' or 'off', "
                             f"got {device_wire!r}")
        self._backend = backend
        self.device_wire = device_wire
        self.lifetime = TransportStats(kind="distributed")
        self._lifetime_lock = threading.Lock()
        # exchanges are collective and issued in program order on every
        # rank, so this per-instance ordinal doubles as the cross-rank
        # sequence tag on the transport.exchange span
        self._seq = itertools.count()

    def _resolve_backend(self, group):
        b = getattr(group, "backend", None)
        if b is not None:
            if self._backend is not None and self._backend is not b:
                raise ValueError(
                    "transport and group are bound to different process "
                    "backends")
            return b
        return self._backend or current_backend() or LocalBackend()

    # -- optional jax.distributed device wire -----------------------------
    def _device_wire_ready(self, backend) -> bool:
        if self.device_wire == "off" or backend.world_size <= 1:
            return False
        import jax

        if not jax.distributed.is_initialized():
            return False
        return (jax.process_count() == backend.world_size
                and jax.device_count() >= backend.world_size
                and len(jax.local_devices()) >= 1)

    def _exchange_rows_device(self, backend, outgoing: list) -> list:
        """Ship the wire entries' row bytes over a process-spanning
        device-mesh ``all_to_all`` (one device per process); manifests
        and shapes ride the control backend.  Returns the incoming
        entry lists (same layout as the serialized wire).  Only taken
        under a real multi-controller ``jax.distributed`` launch; a
        failure on that wire raises rather than falling back to the
        pipes."""
        import jax
        from jax.sharding import (Mesh, NamedSharding,
                                  PartitionSpec as P)

        from ..compat import shard_map

        W = backend.world_size
        # per-dest concatenated byte matrix + control entries that
        # say how to split it back into payload rows
        mats, ctrl = [], []
        for dr in range(W):
            blocks, centries = [], []
            for gid, src, dest, rows, manifest in outgoing[dr]:
                if isinstance(rows, np.ndarray):
                    blocks.append(rows)
                    centries.append((gid, src, dest, manifest,
                                     "mat", rows.shape))
                else:
                    widths = [int(len(r)) for r in rows]
                    wm = max(widths, default=0)
                    m = np.zeros((len(rows), wm), np.uint8)
                    for i, r in enumerate(rows):
                        m[i, :widths[i]] = r
                    blocks.append(m)
                    centries.append((gid, src, dest, manifest,
                                     "rows", widths))
            mats.append(blocks)
            ctrl.append(centries)
        dims = [[(int(b.shape[0]), int(b.shape[1])) for b in blocks]
                for blocks in mats]
        all_dims = backend.alltoall(dims)   # dims[me->dr] lands at dr
        R = max((sum(r for r, _ in per) for per in dims), default=0)
        C = max((c for per in dims for _, c in per), default=0)
        # global padded extents (the collective is dense/static)
        R = int(np.max(backend.allgather(R)))
        C = int(np.max(backend.allgather(C)))
        in_ctrl = backend.alltoall(ctrl)
        if R == 0 or C == 0:
            return [[(g, s, d, [] if k == "rows" else
                      np.zeros((0, 0), np.uint8), mf)
                     for g, s, d, mf, k, _ in in_ctrl[sr]]
                    for sr in range(W)]
        local = np.zeros((W, R, C), np.uint8)
        for dr in range(W):
            off = 0
            for b in mats[dr]:
                local[dr, off:off + b.shape[0], :b.shape[1]] = b
                off += b.shape[0]
        mesh = Mesh(np.asarray(jax.devices())[:W], ("proc",))
        g = jax.make_array_from_single_device_arrays(
            (W * W, R, C), NamedSharding(mesh, P("proc")),
            [jax.device_put(local, jax.local_devices()[0])])
        out = jax.jit(shard_map(
            lambda x: jax.lax.all_to_all(x, "proc", 0, 0, tiled=True),
            mesh=mesh, in_specs=P("proc"), out_specs=P("proc")))(g)
        recv = np.asarray(out.addressable_shards[0].data)  # (W, R, C)
        incoming = []
        for sr in range(W):
            entries, off = [], 0
            for (gid, src, dest, manifest, kind, info), (m, c) in zip(
                    in_ctrl[sr], all_dims[sr]):
                block = recv[sr, off:off + m, :c]
                off += m
                if kind == "mat":
                    rows: Any = block
                else:
                    rows = [block[i, :w] for i, w in enumerate(info)]
                entries.append((gid, src, dest, rows, manifest))
            incoming.append(entries)
        return incoming

    # -- the exchange ------------------------------------------------------
    def exchange(self, group, counts, payloads):
        with telemetry.span("transport.exchange", kind="distributed",
                            seq=next(self._seq)) as sp:
            return self._exchange(group, counts, payloads, sp)

    def _exchange(self, group, counts, payloads, sp):
        backend = self._resolve_backend(group)
        W = backend.world_size
        me = backend.rank
        rank_of = getattr(group, "rank_of", lambda p: 0)
        stats = TransportStats(kind="distributed")

        delivered = []
        outgoing: list[list] = [[] for _ in range(W)]
        for col, src, dest, payload in payloads:
            if rank_of(src) != me:
                raise RuntimeError(
                    f"phase 1 extracted a payload for place {src} owned "
                    f"by rank {rank_of(src)} on rank {me}")
            if src == dest:
                stats.local += 1
                delivered.append((col, src, dest, payload))
                continue
            stats.payloads += 1
            dr = rank_of(dest)
            if dr == me:
                # rank-local cross-place move: reference pass-through,
                # the HostTransport semantics within one process
                delivered.append((col, src, dest, payload))
                continue
            rows, manifest = col.encode_rows(payload)
            if isinstance(rows, np.ndarray) and rows.ndim == 2:
                wire_rows: Any = np.ascontiguousarray(rows)
                m, wmax = int(rows.shape[0]), int(rows.shape[1])
                nb = int(rows.size)
            else:
                wire_rows = [_host_bytes(r) for r in rows]
                widths = [int(r.shape[0]) for r in wire_rows]
                m = len(wire_rows)
                wmax = max(widths, default=0)
                nb = int(sum(widths))
            stats.rows += m
            stats.row_bytes += nb
            stats.wire_bytes += nb
            stats.width = max(stats.width, wmax)
            outgoing[dr].append((col.global_id, src, dest,
                                 wire_rows, manifest))

        if W > 1:
            chaos = getattr(backend, "chaos", None)
            if chaos is not None:
                outgoing = chaos.corrupt_outgoing(outgoing)
            if self._device_wire_ready(backend):
                incoming = self._exchange_rows_device(backend, outgoing)
            else:
                incoming = backend.alltoall(outgoing)
            stats.exchanges += 1
            for sr in range(W):
                if sr == me or incoming[sr] is None:
                    continue
                for gid, src, dest, rows, manifest in incoming[sr]:
                    col = lookup_collection(gid)
                    if col is None:
                        raise RuntimeError(
                            f"no collection with global id {gid} on rank "
                            f"{me} — SPMD programs must create "
                            "collections in the same order on every rank")
                    payload = col.decode_rows(rows, manifest)
                    delivered.append((col, src, dest, payload))

        if sp:
            sp.set(rank=me, world=W)
        _account_exchange(self, stats, sp)
        return delivered, stats
