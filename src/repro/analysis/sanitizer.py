"""Runtime sanitizer for the relocation window data plane.

Three checkers, all opt-in (``REPRO_SANITIZE=1`` in the environment, or
``sanitize=True`` on ``CollectiveMoveManager`` / ``GLBConfig`` /
``run_multiprocess``), all off the hot path when disabled (one module
attribute test per instrumented operation):

* **Race detector** — lockset + happens-before over DistCollection
  mutations versus in-flight window phases.  Window phases come from
  the PR-7 telemetry span stream (``reloc.submit`` → ``reloc.phase1`` →
  ``reloc.deliver`` → ``reloc.commit``, correlated by the ``window``
  context attribute), so the pipeline's existing instrumentation is the
  event source; only the collection-level mutation hooks are new.  The
  invariant: between a window's submission and its delivery, a
  structural mutation of a participating collection must hold that
  collection's ``_lock`` — the lock is what serializes it against the
  background extraction/insertion threads.  A mutation that holds the
  lock is ordered (lockset); a mutation before submit or after delivery
  is ordered (happens-before); anything else is a race, reported
  *at the mutation site* with the collection, operation, and window
  phase named — not 2 windows later as corrupted state.

* **SPMD contract checker** — on process-backed groups every rank must
  register the same move stream (``core/distributed.py``'s window
  contract).  Today drift surfaces as a late collective-tag mismatch or
  a deadlock.  The checker fingerprints the registered stream
  (kind, collection global id, range/count, destination — rule
  callables are opaque and excluded), allgathers the digests *before*
  phase-1 extraction, and on divergence raises with a per-rank diff
  that names the first differing move.

* **Transport invariant assertions** — per window: the §5.3 accounting
  identity (delivered off-place bytes == the counts-matrix column sum
  of the local places), a zero diagonal on the counts matrix, and a
  codec round-trip spot check on one sampled payload row
  (``decode(encode(p))`` re-encodes to identical bytes), so codec drift
  is caught even on transports that never encode (host loopback).

Cost: a digest + one row round-trip per window, a dict probe per
mutation.  The ``reloc_sanitizer_overhead`` benchmark row asserts
sanitized windows stay within 15% of unsanitized wall clock.

This module keeps zero module-level imports from ``repro.core`` so any
core module may import it at module scope without a cycle.
"""
from __future__ import annotations

import hashlib
import os
import threading
from collections import deque

__all__ = [
    "SanitizerError",
    "RelocationRaceError",
    "SPMDContractError",
    "TransportInvariantError",
    "DigestRing",
    "digest_ring",
    "active",
    "enable",
    "disable",
    "fingerprint_moves",
    "check_mutation",
    "check_spmd_contract",
    "check_commit_invariants",
    "check_codec_roundtrip",
    "window_report",
]


class SanitizerError(RuntimeError):
    """Base class: an invariant of the window data plane was violated."""


class RelocationRaceError(SanitizerError):
    """Unlocked mutation of a collection with an in-flight window."""


class SPMDContractError(SanitizerError):
    """Ranks registered diverging move streams for one window."""


class TransportInvariantError(SanitizerError):
    """§5.3 accounting identity or codec round-trip failed."""


# ---------------------------------------------------------------------------
# digest ring — shared diagnostic memory
#
# Records the recent (seq, kind, detail) history of both window digests
# (this module) and backend collectives (PipeBackend feeds it on every
# tagged exchange, sanitized or not — a deque append is ~100ns).  When a
# seq-tag mismatch or contract divergence fires, the tail shows *what*
# the ranks were doing, not just two integers.
# ---------------------------------------------------------------------------
class DigestRing:
    def __init__(self, maxlen: int = 64):
        self._items: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()

    def record(self, seq, kind: str, detail: str | None = None) -> None:
        with self._lock:
            self._items.append((seq, kind, detail))

    def tail(self, n: int = 8) -> list[tuple]:
        with self._lock:
            items = list(self._items)
        return items[-n:]

    def describe(self, n: int = 8) -> str:
        items = self.tail(n)
        if not items:
            return "none"
        return ", ".join(
            f"#{seq}:{kind}" + (f"[{detail}]" if detail else "")
            for seq, kind, detail in items)

    def clear(self) -> None:
        with self._lock:
            self._items.clear()


_RING = DigestRing()


def digest_ring() -> DigestRing:
    return _RING


# ---------------------------------------------------------------------------
# global switch
# ---------------------------------------------------------------------------
# instrumented hot paths test this attribute directly
# (``if _san._ACTIVE: _san.check_mutation(...)``)
_ACTIVE = False

_ENV_FLAG = os.environ.get("REPRO_SANITIZE", "").strip().lower() \
    in ("1", "true", "yes", "on")

_LOCK = threading.Lock()

# window_id -> {"phase": str, "gids": frozenset[int]}
_WINDOWS: dict[int, dict] = {}
# collection global_id -> set of in-flight window ids covering it
_BY_COL: dict[int, set] = {}
# advisory: non-raising findings (codec spot checks run on delivery
# threads where raising is already handled, races raise at the call
# site) — tests and reports read this
_REPORTS: list[str] = []


def active() -> bool:
    """Is the sanitizer on?  ``REPRO_SANITIZE=1`` enables it lazily on
    the first data-plane construction that asks."""
    if _ACTIVE:
        return True
    if _ENV_FLAG:
        enable()
        return True
    return False


def enable(*, rank: int | None = None) -> None:
    """Turn every checker on.  Forces telemetry on (the window-phase
    event source is the span stream) and registers the span listener."""
    global _ACTIVE
    from ..core import telemetry

    with _LOCK:
        telemetry.enable(rank=rank)
        telemetry.tracer().add_listener(_on_span_record)
        _ACTIVE = True


def disable() -> None:
    """Turn the sanitizer off and drop its window state.  Telemetry is
    left as-is (the caller may have enabled it independently)."""
    global _ACTIVE
    from ..core import telemetry

    with _LOCK:
        _ACTIVE = False
        telemetry.tracer().remove_listener(_on_span_record)
        _WINDOWS.clear()
        _BY_COL.clear()
        del _REPORTS[:]


def window_report() -> dict:
    """Diagnostic snapshot: in-flight windows, per-collection coverage,
    and advisory findings."""
    with _LOCK:
        return {
            "windows": {w: dict(st) for w, st in _WINDOWS.items()},
            "by_collection": {g: sorted(w) for g, w in _BY_COL.items()},
            "reports": list(_REPORTS),
        }


# ---------------------------------------------------------------------------
# window phase tracking — fed by the telemetry span stream
# ---------------------------------------------------------------------------
def _window_of(ctx, attrs):
    if attrs and "window" in attrs:
        return attrs["window"]
    if ctx and "window" in ctx:
        return ctx["window"]
    return None


def _on_span_record(rec) -> None:
    """Tracer listener (called on the recording thread, after the ring
    write).  Must never raise — race errors fire at mutation sites, not
    from inside a span's ``__exit__``."""
    try:
        name, _ph, _ts, _dur, ctx, attrs, _rank, _ident = rec
        if not name.startswith("reloc."):
            return
        w = _window_of(ctx, attrs)
        if w is None:
            return
        if name == "reloc.submit":
            gids = frozenset(attrs.get("gids", ()))
            with _LOCK:
                _WINDOWS[w] = {"phase": "phase1", "gids": gids}
                for g in gids:
                    _BY_COL.setdefault(g, set()).add(w)
        elif name == "reloc.phase1":
            with _LOCK:
                st = _WINDOWS.get(w)
                if st is not None:
                    if attrs and "error" in attrs:
                        # failed + rolled back: nothing in flight anymore
                        _close_window_locked(w)
                    else:
                        st["phase"] = "extracted"
        elif name == "reloc.deliver":
            # payloads have landed (insertions run under each
            # collection's lock) — collections leave the danger zone
            with _LOCK:
                st = _WINDOWS.get(w)
                if st is not None:
                    st["phase"] = "delivered"
                    for g in st["gids"]:
                        wins = _BY_COL.get(g)
                        if wins is not None:
                            wins.discard(w)
                            if not wins:
                                _BY_COL.pop(g, None)
        elif name == "reloc.commit":
            with _LOCK:
                _close_window_locked(w)
    except Exception:
        pass


def _close_window_locked(w) -> None:
    st = _WINDOWS.pop(w, None)
    if st is None:
        return
    for g in st["gids"]:
        wins = _BY_COL.get(g)
        if wins is not None:
            wins.discard(w)
            if not wins:
                _BY_COL.pop(g, None)


# ---------------------------------------------------------------------------
# race detector — mutation-site hook
# ---------------------------------------------------------------------------
def check_mutation(col, op: str, detail=None) -> None:
    """Called by ``core/collections.py`` mutators when the sanitizer is
    active.  Raises :class:`RelocationRaceError` when ``col`` has an
    in-flight window (submitted, not yet delivered) and the calling
    thread does not hold the collection lock."""
    wins = _BY_COL.get(col.global_id)
    if not wins:
        return
    is_owned = getattr(col._lock, "_is_owned", None)
    if is_owned is None or is_owned():
        return  # lockset: serialized against the window threads
    with _LOCK:
        live = [(w, _WINDOWS[w]["phase"]) for w in sorted(wins)
                if w in _WINDOWS]
    if not live:
        return
    w, phase = live[0]
    what = f"{op}({detail!r})" if detail is not None else f"{op}()"
    raise RelocationRaceError(
        f"unlocked mutation {what} of {type(col).__name__}"
        f"#{col.global_id} while relocation window {w} is in flight "
        f"(phase={phase}): between sync_async() and delivery, "
        "structural mutation must hold the collection's _lock — the "
        "window's background extraction/insertion threads serialize on "
        "it.  Take `with col._lock:` around the mutation, or finish() "
        "the window first.")


# ---------------------------------------------------------------------------
# SPMD contract checker
# ---------------------------------------------------------------------------
def fingerprint_moves(moves) -> list[str]:
    """Canonical one-line descriptors of a window's registered move
    stream — everything that must agree rank-to-rank.  Key-move *rules*
    are callables (opaque): the key-move line carries collection + src
    only, so rule divergence is out of scope (documented)."""
    range_moves, array_count_moves, bag_moves, key_moves = moves
    descs = []
    for m in range_moves:
        descs.append(f"range gid={m.collection.global_id} "
                     f"[{m.r.start},{m.r.end}) -> {m.dest}")
    for m in array_count_moves:
        descs.append(f"acount gid={m.collection.global_id} src={m.src} "
                     f"n={m.count} -> {m.dest}")
    for m in bag_moves:
        descs.append(f"bag gid={m.collection.global_id} src={m.src} "
                     f"n={m.count} -> {m.dest}")
    for m in key_moves:
        descs.append(f"key gid={m.collection.global_id} src={m.src}")
    return descs


def _digest(descs) -> str:
    h = hashlib.sha1("\n".join(descs).encode()).hexdigest()
    return h[:16]


_MAX_DIFF_DESCS = 64


def check_spmd_contract(group, moves, window_id) -> None:
    """Allgather per-rank move-stream digests before phase-1 extraction;
    raise with a per-rank diff on divergence.  Collective — every rank
    of a sanitized run reaches this at the same point of its phase-1
    (the sanitize flag must agree across ranks, like any collective).

    In-process groups have no wire and no ranks to diverge, so the
    whole check (fingerprint included) is skipped — windows there pay
    nothing for it."""
    backend = getattr(group, "backend", None)
    if backend is None or not group.process_backed:
        return
    descs = fingerprint_moves(moves)
    digest = _digest(descs)
    _RING.record(window_id, "window", digest)
    gathered = backend.allgather((digest, descs[:_MAX_DIFF_DESCS]))
    gathered = [g if g is not None else ("<dead rank>", [])
                for g in gathered]   # dead ranks can't diverge
    if len({d for d, _ in gathered if d != "<dead rank>"}) <= 1:
        return
    me = backend.rank
    lines = [
        f"SPMD window contract violated in window {window_id}: ranks "
        "registered diverging move streams (every rank must register "
        "the same moves, in the same order — src-explicit moves "
        "included; only the owning rank extracts them).  Without the "
        "sanitizer this surfaces later as a collective-tag mismatch or "
        "a deadlock.  Per-rank move streams:"
    ]
    ref_digest, ref_descs = gathered[0]
    for r, (d, rd) in enumerate(gathered):
        n = len(rd)
        marker = " (this rank)" if r == me else ""
        lines.append(f"  rank {r}{marker}: digest={d} moves={n}"
                     + ("" if n < _MAX_DIFF_DESCS else "+"))
        if d != ref_digest:
            for i in range(max(len(rd), len(ref_descs))):
                a = ref_descs[i] if i < len(ref_descs) else "<none>"
                b = rd[i] if i < len(rd) else "<none>"
                if a != b:
                    lines.append(f"    first divergence at move {i}: "
                                 f"rank 0 registered `{a}`, "
                                 f"rank {r} registered `{b}`")
                    break
    lines.append(f"  recent digest-ring tail: {_RING.describe()}")
    raise SPMDContractError("\n".join(lines))


# ---------------------------------------------------------------------------
# transport invariant assertions
# ---------------------------------------------------------------------------
def check_commit_invariants(manager, counts, moved_bytes,
                            window_id) -> None:
    """§5.3 accounting: the diagonal never reaches the wire, and the
    delivered off-place bytes equal the counts destined to this rank's
    local places (== the whole matrix sum in-process)."""
    import numpy as np

    if counts is None:
        return
    counts = np.asarray(counts)
    diag = int(np.abs(np.diagonal(counts)).sum())
    if diag != 0:
        raise TransportInvariantError(
            f"window {window_id}: counts matrix has nonzero diagonal "
            f"({diag} bytes) — self-moves must never reach the wire "
            "accounting (core/relocation.py phase-1 contract)")
    group = manager.group
    place_index = {p: i for i, p in enumerate(group.members)}
    local_idx = [place_index[p] for p in group.local_places()]
    expected = int(counts[:, local_idx].sum())
    if int(moved_bytes) != expected:
        raise TransportInvariantError(
            f"window {window_id}: delivered off-place payload bytes "
            f"({int(moved_bytes)}) != counts destined to local places "
            f"({expected}) — the two §5.3 accounting surfaces "
            "(phase-1 counts matrix vs delivered payloads) must agree "
            "on every transport; a mismatch means a payload was "
            "dropped, duplicated, or re-measured differently at the "
            "destination")


def _rows_bytes(rows):
    import numpy as np

    from ..core.collections import _host_bytes

    if isinstance(rows, np.ndarray):
        return [rows.tobytes()]
    return [_host_bytes(r).tobytes() for r in rows]


def _sample_row_payload(payload, window_id):
    """A one-entry sub-payload of ``payload`` (row picked by window id)
    in the owning collection's own payload shape, or ``None`` when the
    shape is unknown/empty.  Keeps the spot check O(1 row) however
    large the window."""
    if isinstance(payload, tuple) and len(payload) == 2 \
            and hasattr(payload[0], "start"):       # DistArray: (range, rows)
        r, rows = payload
        n = len(rows)
        if n == 0:
            return None
        i = window_id % n
        return (type(r)(r.start + i, r.start + i + 1), rows[i:i + 1])
    if isinstance(payload, list):                   # bag items / map pairs
        if not payload:
            return None
        i = window_id % len(payload)
        return payload[i:i + 1]
    return None


# spot-check cadence: round-trip every Nth window (window ids are a
# global monotone counter, so this is deterministic and drift shows up
# within N windows).  Tests pin it to 1 to make every window checked.
_CODEC_SAMPLE_EVERY = 4


def check_codec_roundtrip(payloads, window_id) -> None:
    """Spot check: sample ONE row of ONE payload (both picked
    deterministically by window id) and round-trip it through the
    owning collection's row codec — ``encode → decode → encode`` must
    reproduce identical row bytes.  Catches codec drift even on
    transports that never encode (host loopback), at O(1-row) cost on
    every ``_CODEC_SAMPLE_EVERY``-th window, however large the
    exchange."""
    if not payloads or window_id % _CODEC_SAMPLE_EVERY:
        return
    col, src, dest, payload = payloads[window_id % len(payloads)]
    sample = _sample_row_payload(payload, window_id)
    if sample is None:
        return
    try:
        rows1, manifest1 = col.encode_rows(sample)
        decoded = col.decode_rows(rows1, manifest1)
        rows2, _manifest2 = col.encode_rows(decoded)
        b1, b2 = _rows_bytes(rows1), _rows_bytes(rows2)
    except SanitizerError:
        raise
    except Exception as e:
        raise TransportInvariantError(
            f"window {window_id}: codec round-trip raised for "
            f"{type(col).__name__}#{col.global_id} payload "
            f"{src}->{dest}: {type(e).__name__}: {e}") from e
    if b1 != b2:
        raise TransportInvariantError(
            f"window {window_id}: codec round-trip mismatch for "
            f"{type(col).__name__}#{col.global_id} payload "
            f"{src}->{dest}: decode(encode(p)) re-encodes to different "
            "bytes — the destination would reconstruct a different "
            "payload than the source shipped")
