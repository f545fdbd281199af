"""Relocatable distributed collections (paper §3, Table 1).

Local-handle semantics: a distributed collection is a set of *local
handles*, one per place, linked by a global id.  All reads/writes go
through a place's own handle; anything that crosses places is a *teamed
operation* (relocation, gather, broadcast, reduction — see
``relocation.py`` / ``teamed.py``).

On a TPU cluster a "place" is a mesh device (or a mesh-axis coordinate)
and the handle's chunks are that device's shard.  This module keeps the
handles host-side (numpy) so the distribution logic is runnable and
testable anywhere; ``to_device``/``from_device`` bridge a collection to
a sharded ``jax.Array`` for jitted compute, mirroring the paper's
separation between the collection runtime (Java heap) and the compute
it feeds.

Lazy handle allocation (paper §5.1) is preserved: handles materialize
on first touch of a place, not at construction.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Sequence

import numpy as np

from ..analysis import sanitizer as _san
from .distribution import LongRange, RangeDistribution

__all__ = [
    "PlaceGroup",
    "DistArray",
    "DistBag",
    "DistMap",
    "DistIdMap",
    "DistMultiMap",
    "CachableArray",
    "CachableChunkedList",
]

_GLOBAL_ID_LOCK = threading.Lock()
_NEXT_GLOBAL_ID = [0]

# global_id → live collection, for transports that address collections
# by id across OS processes (DistributedTransport).  Weak so a dropped
# collection is not pinned by its wire address.
import weakref

_COLLECTIONS = weakref.WeakValueDictionary()


def _fresh_global_id() -> int:
    with _GLOBAL_ID_LOCK:
        _NEXT_GLOBAL_ID[0] += 1
        return _NEXT_GLOBAL_ID[0]


def lookup_collection(global_id: int):
    """The live collection registered under ``global_id`` in this
    process, or ``None``.  SPMD programs create collections in the same
    order on every process, so ids agree rank-to-rank — this is the
    receive-side address resolution of ``DistributedTransport``."""
    return _COLLECTIONS.get(int(global_id))


def unique_leaves_nbytes(leaves, seen: set) -> int:
    """Total bytes of ``leaves`` counting each distinct buffer once
    (dedup by object identity — the single definition the §5.3 byte
    accounting and ``SeqKV.nbytes`` both rest on)."""
    total = 0
    for leaf in leaves:
        if id(leaf) in seen:
            continue
        seen.add(id(leaf))
        lb = getattr(leaf, "nbytes", None)
        total += int(lb) if lb is not None else int(np.asarray(leaf).nbytes)
    return total


def _value_nbytes(x, _seen: set | None = None) -> int:
    """Payload size without forcing a device→host transfer: device
    arrays (and pytree payloads exposing ``nbytes``, e.g. the serving
    tier's per-sequence KV shards) report their size directly.

    Pytree values count each distinct buffer **once**: two leaves that
    alias the same array object (a KV page shared between attention
    groups, say) are one buffer on any real wire, so they are one buffer
    in the §5.3 accounting too.  ``_seen`` extends the dedup across the
    values of one payload."""
    if _seen is None:
        nb = getattr(x, "nbytes", None)
        if nb is not None:
            return int(nb)
    elif isinstance(x, (np.ndarray, np.generic)):
        # plain buffer value: id-dedup without paying a pytree flatten
        # (this runs per entry, twice per window, on the delivery path)
        if id(x) in _seen:
            return 0
        _seen.add(id(x))
        return int(x.nbytes)
    import jax

    if _seen is not None and isinstance(x, jax.Array):
        if id(x) in _seen:
            return 0
        _seen.add(id(x))
        return int(x.nbytes)
    leaves = jax.tree_util.tree_leaves(x)
    if len(leaves) == 1 and leaves[0] is x:
        if _seen is not None:
            if id(x) in _seen:
                return 0
            _seen.add(id(x))
        nb = getattr(x, "nbytes", None)
        return int(nb) if nb is not None else int(np.asarray(x).nbytes)
    return unique_leaves_nbytes(leaves,
                                _seen if _seen is not None else set())


# ---------------------------------------------------------------------------
# Row codecs (transport layer, §5.3 Alltoallv payload encoding)
#
# A collection's payloads are Python structures (chunk arrays, key/value
# pairs, pytrees of device buffers).  A *row codec* maps each payload to
# fixed-width rows + a host-side manifest, so any transport — in
# particular the device ``all_to_all`` of ``core/transport.py`` — can
# ship them without knowing the collection's internals, and the receiver
# can rebuild a bit-identical payload.  Encoding is alias-aware: leaves
# that alias one buffer encode (and ship) once, and decoding rebinds
# them, so both the §5.3 byte accounting and the reconstructed aliasing
# match the source exactly.
#
# A row is host uint8 bytes or device uint32 words (the TPU cannot hold
# byte views without padding them many times over; see
# ``kernels/reloc_codec.py``).  The two are one format: a byte row is
# the little-endian view of its words, and every pytree leaf starts on
# a word boundary.
# ---------------------------------------------------------------------------
def _dtype_token(dt) -> str:
    """Manifest-safe dtype spelling: ``.str`` (endianness-exact) when it
    round-trips, else ``.name`` — numpy extension dtypes (ml_dtypes
    bfloat16/fp8) stringify as raw void ('<V2') through ``.str`` and
    would silently decode as the wrong type."""
    dt = np.dtype(dt)
    return dt.str if np.dtype(dt.str) == dt else dt.name


def _np_bytes(a) -> np.ndarray:
    """1-D uint8 view-copy of an array's bytes (any layout/dtype)."""
    a = np.ascontiguousarray(np.asarray(a))
    return np.frombuffer(a.tobytes(), np.uint8)


def _np_from_bytes(row, dtype, shape) -> np.ndarray:
    nb = int(np.dtype(dtype).itemsize * np.prod(shape, dtype=np.int64))
    buf = _host_bytes(row)[:nb]
    return np.frombuffer(buf.tobytes(), dtype=dtype).reshape(shape).copy()


def _host_bytes(row) -> np.ndarray:
    """A wire row (host bytes, or uint32 words on host or device) as
    host uint8 bytes — the little-endian view of the words."""
    a = np.asarray(row)
    if a.dtype != np.uint8:
        a = np.ascontiguousarray(a).view(np.uint8)
    return a


def _row_words(row):
    """A wire row as uint32 words: device words stay on device, host
    bytes are viewed (zero-padded to a whole word)."""
    import jax

    if isinstance(row, jax.Array):
        if row.dtype == np.uint32:
            return row
        import jax.numpy as jnp

        u8 = jnp.asarray(row, jnp.uint8).reshape(-1)
        u8 = jnp.pad(u8, (0, (-u8.shape[0]) % 4))
        return jax.lax.bitcast_convert_type(u8.reshape(-1, 4), jnp.uint32)
    a = _host_bytes(row).reshape(-1)
    return np.pad(a, (0, (-a.shape[0]) % 4)).view(np.uint32)


def _padded(nb: int) -> int:
    """Bytes a leaf of ``nb`` bytes occupies in a row (whole words)."""
    return -(-int(nb) // 4) * 4


def _jax_leaf_words(x):
    """Device-side word view of a jax leaf (no host transfer)."""
    from ..kernels.reloc_codec import to_words

    return to_words(x.reshape(-1))


def _leaf_from_words(words, dtype, shape):
    """Inverse of :func:`_jax_leaf_words`; stays on device when
    ``words`` is a device buffer, else decodes on host and puts the
    leaf back on the device."""
    import jax

    from ..kernels.reloc_codec import from_words

    n = int(np.prod(shape, dtype=np.int64))
    leaf = from_words(words, np.dtype(dtype), n).reshape(shape)
    return leaf if isinstance(leaf, jax.Array) else jax.device_put(leaf)


def _encode_value(v) -> tuple[Any, tuple]:
    """One map/bag value → (1-D byte row, spec).

    * plain host array → raw bytes (``("arr", dtype, shape, nbytes)``);
    * pytree of array leaves (``SeqKV``, decode-state dicts, multimap
      lists) → unique-leaf bytes concatenated, device-side (bitcast +
      concat, no host bounce) when every leaf is a ``jax.Array``
      (``("tree", treedef, leafspecs, alias, nbytes)``);
    * anything else (e.g. ``serving.Sequence`` metadata) → pickle.
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(v)
    plain_leaf = len(leaves) == 1 and leaves[0] is v
    if plain_leaf and isinstance(v, np.generic) \
            and not np.asarray(v).dtype.hasobject:
        # numpy scalars decode back to scalars, not 0-d arrays —
        # receivers may hash or compare them, and parity with the host
        # loopback (which delivers the original object) demands it
        row = _np_bytes(v)
        return row, ("num", _dtype_token(np.asarray(v).dtype), len(row))
    if plain_leaf and isinstance(v, np.ndarray) \
            and not v.dtype.hasobject:
        row = _np_bytes(v)
        return row, ("arr", _dtype_token(v.dtype), v.shape, len(row))
    # object-dtype arrays hold pointers, not bytes — pickle those whole
    arrayish = all(isinstance(x, jax.Array) or
                   (isinstance(x, (np.ndarray, np.generic))
                    and not np.asarray(x).dtype.hasobject) for x in leaves)
    if leaves and arrayish and (not plain_leaf or isinstance(v, jax.Array)):
        uniq: list = []
        index: dict[int, int] = {}
        alias: list[int] = []
        for x in leaves:
            j = index.get(id(x))
            if j is None:
                j = len(uniq)
                index[id(x)] = j
                uniq.append(x)
            alias.append(j)
        specs, pieces = [], []
        for x in uniq:
            if isinstance(x, jax.Array):
                pieces.append(_jax_leaf_words(x))
                # dtype by *name*: ml_dtypes extensions (bfloat16, fp8)
                # round-trip through np.dtype(name), their .str does not
                specs.append(("jax", np.dtype(x.dtype).name,
                              tuple(x.shape), int(x.nbytes)))
            else:
                a = np.asarray(x)
                b = _np_bytes(a)
                pieces.append(np.pad(b, (0, _padded(b.size) - b.size)))
                specs.append(("nps" if isinstance(x, np.generic) else "np",
                              _dtype_token(a.dtype), a.shape,
                              int(a.nbytes)))
        total = int(sum(_padded(s[3]) for s in specs))
        if any(isinstance(p, jax.Array) for p in pieces):
            import jax.numpy as jnp
            row = jnp.concatenate(
                [p if isinstance(p, jax.Array) else jnp.asarray(p.view(
                    np.uint32)) for p in pieces])
        else:
            row = np.concatenate(pieces) if pieces \
                else np.zeros((0,), np.uint8)
        return row, ("tree", treedef, tuple(specs), tuple(alias), total)
    import pickle

    blob = pickle.dumps(v)
    return np.frombuffer(blob, np.uint8), ("pkl", len(blob))


def _decode_value(row, spec):
    """Inverse of :func:`_encode_value`; ``row`` may be longer than the
    encoded width (transport padding) and may be a device buffer."""
    import jax

    kind = spec[0]
    if kind == "arr":
        _, dt, shape, _ = spec
        return _np_from_bytes(row, np.dtype(dt), shape)
    if kind == "num":
        _, dt, _ = spec
        return _np_from_bytes(row, np.dtype(dt), ())[()]
    if kind == "pkl":
        import pickle

        _, nb = spec
        return pickle.loads(_host_bytes(row)[:nb].tobytes())
    _, treedef, specs, alias, _ = spec
    words = _row_words(row)
    uniq, off = [], 0
    for lkind, dt, shape, nb in specs:
        seg = words[off // 4:(off + _padded(nb)) // 4]
        if lkind == "jax":
            uniq.append(_leaf_from_words(seg, dt, shape))
        else:
            leaf = _np_from_bytes(seg, np.dtype(dt), shape)
            uniq.append(leaf[()] if lkind == "nps" else leaf)
        off += _padded(nb)
    return jax.tree_util.tree_unflatten(treedef, [uniq[j] for j in alias])


class PlaceGroup:
    """Paper's ``TeamedPlaceGroup``: an ordered set of places.

    Optionally bound to a JAX mesh axis so SPMD teamed operations know
    which named axis carries the group's collectives (the analogue of
    the embedded MPI communicator).
    """

    def __init__(self, n_places: int, *, mesh=None, axis: str | None = None,
                 members: Sequence[int] | None = None):
        self.n_places = int(n_places)
        self.mesh = mesh
        self.axis = axis
        self.members = tuple(members) if members is not None else tuple(range(n_places))
        if len(self.members) != self.n_places:
            raise ValueError("members length must equal n_places")

    #: single-process groups: every place is local and rank 0 owns all.
    #: ``ProcessPlaceGroup`` (``core/distributed.py``) overrides these so
    #: the relocation engine can ask *where* a place lives without caring
    #: whether the group spans OS processes.
    process_backed = False

    @staticmethod
    def world(n_places: int, **kw) -> "PlaceGroup":
        return PlaceGroup(n_places, **kw)

    def subgroup(self, members: Sequence[int]) -> "PlaceGroup":
        """Paper §3.4: teamed ops over a subset of the world.

        A *proper* subset drops the parent's ``mesh``/``axis`` binding:
        the named axis spans every parent member, so device collectives
        issued "for the subgroup" would actually run over the full axis
        — silently wrong results, not an error.  Sub-axis teams need
        their own mesh; until one is bound, the subgroup is host-only."""
        members = tuple(members)
        full = members == self.members
        return PlaceGroup(len(members),
                          mesh=self.mesh if full else None,
                          axis=self.axis if full else None,
                          members=members)

    def size(self) -> int:
        return self.n_places

    # -- process topology (trivial for in-process groups) -----------------
    def rank_of(self, place: int) -> int:
        """OS-process rank owning ``place`` (always 0 in-process)."""
        return 0

    def is_local(self, place: int) -> bool:
        """Does ``place``'s handle live in this process?"""
        return True

    def local_places(self) -> tuple:
        """The members whose handles live in this process."""
        return self.members

    def exchange_counts(self, counts: np.ndarray) -> np.ndarray:
        """Phase-1 Alltoall of the place×place byte-count matrix: the
        in-process group already sees the global matrix."""
        return counts

    def exchange_range_claims(self, claims: Sequence[int]) -> list[int]:
        """Per-range-move locally-covered entry counts, summed across
        processes (identity in-process)."""
        return [int(c) for c in claims]

    def __contains__(self, place: int) -> bool:
        return place in self.members

    def __repr__(self) -> str:
        return f"PlaceGroup({list(self.members)})"


class _CommStats:
    """Communication accounting shared by teamed operations so the
    benchmarks can report Alltoall/Alltoallv-equivalent volumes."""

    def __init__(self):
        self.bytes_moved = 0
        self.messages = 0
        self.syncs = 0

    def record(self, nbytes: int, messages: int = 1) -> None:
        self.bytes_moved += int(nbytes)
        self.messages += int(messages)

    def reset(self) -> None:
        self.bytes_moved = 0
        self.messages = 0
        self.syncs = 0


class DistCollection:
    """Base: global id, place group, lazily-allocated local handles.

    ``_lock`` serializes structural mutation of the handles across the
    relocation engine's background threads: with double-buffered windows
    (``sync_async(depth=2)``) window N's *delivery* runs concurrently
    with window N+1's *extraction* — both against the same handles — and
    with main-thread inserts (serving admission).  Pure reads stay
    lock-free, as before: they tolerate concurrent pops/inserts by
    snapshotting (``list(h)``) and ``get``-ing.
    """

    def __init__(self, group: PlaceGroup):
        self.group = group
        self.global_id = _fresh_global_id()
        _COLLECTIONS[self.global_id] = self
        self._handles: dict[int, Any] = {}
        self._lock = threading.RLock()
        self.comm = _CommStats()

    # -- lazy allocation (paper §5.1) ---------------------------------
    def _new_handle(self) -> Any:  # pragma: no cover - abstract
        raise NotImplementedError

    def handle(self, place: int):
        """The local handle of ``place``; allocated on first touch."""
        if place not in self.group:
            raise KeyError(f"place {place} not in {self.group}")
        h = self._handles.get(place)
        if h is None:
            h = self._new_handle()
            self._handles[place] = h
        return h

    def allocated_places(self) -> list[int]:
        return sorted(self._handles)


# ---------------------------------------------------------------------------
# DistArray : DistChunkedList / DistCol
# ---------------------------------------------------------------------------
class _ChunkHandle:
    """A place's chunks: disjoint ``LongRange`` → ndarray of rows."""

    def __init__(self):
        self.chunks: dict[LongRange, np.ndarray] = {}

    def ranges(self) -> list[LongRange]:
        return sorted(self.chunks, key=lambda r: r.start)

    def size(self) -> int:
        return sum(r.size for r in self.chunks)

    def get(self, idx: int) -> np.ndarray:
        for r, arr in self.chunks.items():
            if r.contains(idx):
                return arr[idx - r.start]
        raise KeyError(idx)

    def set(self, idx: int, value) -> None:
        for r, arr in self.chunks.items():
            if r.contains(idx):
                arr[idx - r.start] = value
                return
        raise KeyError(idx)

    def add_chunk(self, r: LongRange, arr: np.ndarray) -> None:
        if r.size != len(arr):
            raise ValueError(f"chunk {r} size != array length {len(arr)}")
        for existing in self.chunks:
            if existing.overlaps(r):
                raise ValueError(f"chunk {r} overlaps existing {existing}")
        self.chunks[r] = np.asarray(arr)

    def intersections(self, r: LongRange) -> list[LongRange]:
        """Locally-held sub-ranges of ``r``, sorted by start."""
        inters = [cr.intersection(r) for cr in self.chunks]
        return sorted((i for i in inters if i is not None),
                      key=lambda i: i.start)

    def extract(self, r: LongRange) -> np.ndarray:
        """Remove and return rows covering ``r`` (splits chunks as needed,
        paper §5.2: 'existing chunks will be split as necessary').

        Coverage is validated *before* any chunk is popped: a partial
        hold raises with the handle untouched, so a failed relocation
        window never destroys the entries it could not move."""
        inters = self.intersections(r)
        if not inters:
            raise KeyError(f"range {r} not held locally")
        covered = sum(i.size for i in inters)
        if covered != r.size or inters[0].start != r.start:
            raise KeyError(f"range {r} only partially held locally")
        taken = []
        for cr in list(self.chunks):
            inter = cr.intersection(r)
            if inter is None:
                continue
            arr = self.chunks.pop(cr)
            lo = inter.start - cr.start
            hi = inter.end - cr.start
            taken.append((inter.start, arr[lo:hi]))
            if lo > 0:
                self.chunks[LongRange(cr.start, inter.start)] = arr[:lo]
            if hi < cr.size:
                self.chunks[LongRange(inter.end, cr.end)] = arr[hi:]
        taken.sort(key=lambda t: t[0])
        return np.concatenate([a for _, a in taken], axis=0)


class DistArray(DistCollection):
    """Paper's ``DistChunkedList`` / ``DistCol``: a long-indexed array
    whose rows live in per-place chunks; with tracked distribution.

    ``track=True`` gives ``DistCol`` semantics (ownership table kept &
    reconciled through :meth:`update_dist`); ``track=False`` is the
    plain ``DistChunkedList``.
    """

    def __init__(self, group: PlaceGroup, *, track: bool = True):
        super().__init__(group)
        self.track = track
        self._dist = RangeDistribution() if track else None
        self._dist_versions = {p: 0 for p in group.members}
        self.update_bytes = 0  # delta traffic accounting for updateDist

    def _new_handle(self) -> _ChunkHandle:
        return _ChunkHandle()

    # -- local access ---------------------------------------------------
    def add_chunk(self, place: int, r: LongRange, rows) -> None:
        with self._lock:
            self.handle(place).add_chunk(r, np.asarray(rows))
            if self.track:
                self._dist.assign(r, place)

    def get(self, place: int, idx: int):
        return self.handle(place).get(idx)

    def set(self, place: int, idx: int, value) -> None:
        if _san._ACTIVE:
            _san.check_mutation(self, "set", idx)
        self.handle(place).set(idx, value)

    def ranges(self, place: int) -> list[LongRange]:
        return self.handle(place).ranges()

    def local_size(self, place: int) -> int:
        return self.handle(place).size()

    def global_size(self) -> int:
        return sum(self.handle(p).size() for p in self.group.members)

    # -- parallel patterns (intra-node parallelism, paper §3.5) ---------
    def for_each(self, place: int, fn: Callable[[int, np.ndarray], None]) -> None:
        for r in self.ranges(place):
            arr = self.handle(place).chunks[r]
            for i in range(r.size):
                fn(r.start + i, arr[i])

    def map_chunks(self, place: int, fn: Callable[[np.ndarray], np.ndarray]) -> None:
        """`parallelForEach` analogue: fn is applied per chunk (the
        vectorized/thread-free TPU equivalent of per-thread scheduling)."""
        if _san._ACTIVE:
            _san.check_mutation(self, "map_chunks")
        h = self.handle(place)
        for r in list(h.chunks):
            h.chunks[r] = np.asarray(fn(h.chunks[r]))

    def to_local_matrix(self, place: int) -> tuple[np.ndarray, np.ndarray]:
        """Pack the place's rows into one dense (n, ...) matrix + the
        global indices. Bridge toward a device shard."""
        h = self.handle(place)
        rs = h.ranges()
        if not rs:
            return np.zeros((0,)), np.zeros((0,), np.int64)
        rows = np.concatenate([h.chunks[r] for r in rs], axis=0)
        idx = np.concatenate([np.arange(r.start, r.end) for r in rs])
        return rows, idx

    # -- device bridge (collection runtime ↔ jitted compute) -------------
    def to_device(self, place: int):
        """Pack the place's rows into a device shard: a ``jax.Array``
        of the local rows plus their global indices (host).  The shard
        feeds jitted compute; :meth:`from_device` writes results back
        into the same chunk layout."""
        import jax

        rows, idx = self.to_local_matrix(place)
        return jax.device_put(rows), idx

    def from_device(self, place: int, rows, idx=None) -> None:
        """Write a device shard's rows back into the place's chunks (the
        inverse of :meth:`to_device`; the chunk layout must not have
        changed in between — relocation windows go through the move
        manager, never through this bridge).  Pass the ``idx`` array
        :meth:`to_device` returned to verify the layout exactly: a
        relocation can swap equal-*sized* ranges, which a bare row-count
        check cannot see."""
        if _san._ACTIVE:
            _san.check_mutation(self, "from_device")
        h = self.handle(place)
        rows = np.asarray(rows)
        if len(rows) != h.size():
            raise ValueError(
                f"device shard holds {len(rows)} rows but place {place} "
                f"holds {h.size()} — layout changed under the bridge")
        if idx is not None:
            cur = np.concatenate(
                [np.arange(r.start, r.end) for r in h.ranges()]) \
                if h.ranges() else np.zeros((0,), np.int64)
            if len(idx) != len(cur) or not np.array_equal(idx, cur):
                raise ValueError(
                    f"place {place} holds different indices than the "
                    f"device shard — layout changed under the bridge")
        off = 0
        for r in h.ranges():
            h.chunks[r] = np.asarray(rows[off:off + r.size])
            off += r.size

    # -- relocation registration (paper §5.2, RangeRelocatable) ---------
    def move_range_at_sync(self, r: LongRange, dest: int, mm) -> None:
        mm.register_range_move(self, r, dest)

    def move_at_sync_count(self, place: int, count: int, dest: int, mm) -> None:
        """Bulk relocation: library picks the entries at sync time
        (paper §5.2) — several count moves from one source compose."""
        mm.register_array_count_move(self, place, count, dest)

    # -- distribution tracking (paper §4.6) ------------------------------
    def get_distribution(self) -> RangeDistribution:
        if not self.track:
            raise ValueError("distribution tracking disabled for this collection")
        with self._lock:
            return self._dist.copy()

    def update_dist(self) -> None:
        """Teamed reconciliation. Host model: rebuild from handles while
        accounting the delta bytes that the wire protocol would move
        (only changes since each place's last sync — paper §4.6).  May
        run on a double-buffered window's delivery thread, so the whole
        rebuild-and-swap holds the collection lock."""
        if not self.track:
            raise ValueError("distribution tracking disabled")
        with self._lock:
            old = self._dist
            new = RangeDistribution()
            local = {p: self.ranges(p) for p in self.group.local_places()}
            if self.group.process_backed:
                # teamed: every rank contributes its local ownership and
                # receives the merged table (collective — all ranks must
                # reconcile the same collections in the same order)
                merged: dict = {}
                for part in self.group.backend.allgather(local):
                    if part is not None:   # dead ranks contribute nothing
                        merged.update(part)
                local = merged
            for p, ranges in local.items():
                for r in ranges:
                    new.assign(r, p)
            # Delta accounting: ranges whose ownership changed since `old`.
            changed = 0
            for r, o in new.items():
                try:
                    prev_owner = old.owner_of(r.start)
                except KeyError:
                    prev_owner = -2
                if prev_owner != o:
                    changed += 1
            self.update_bytes += 8 * 3 * changed * self.group.size()
            self.comm.record(8 * 3 * changed * self.group.size(),
                             messages=self.group.size())
            self._dist = new

    # -- relocation execution hooks (called by CollectiveMoveManager) ----
    def _extract_range(self, r: LongRange, src: int) -> np.ndarray:
        return self.handle(src).extract(r)

    def _insert_payload(self, dest: int, payload) -> None:
        r, rows = payload
        self.handle(dest).add_chunk(r, rows)

    def _payload_nbytes(self, payload) -> int:
        _, rows = payload
        return int(np.asarray(rows).nbytes) + 16

    # -- row codec (transport layer) -------------------------------------
    def encode_rows(self, payload, *, donate: bool = False):
        """Chunk payload → ``(m, width)`` uint8 row matrix + manifest
        (range, dtype, trailing shape) — the §5.3 Alltoallv wire format
        a :class:`~repro.core.transport.DeviceTransport` ships.

        ``donate=True`` is the buffer-donation fast path: the caller
        promises not to mutate the payload while the rows are live, so
        the matrix is a zero-copy ``view`` of the chunk bytes instead
        of a ``tobytes`` copy — what the transport (which packs the
        rows into the send buffer immediately) always wants."""
        r, rows = payload
        a = np.ascontiguousarray(np.asarray(rows))
        m = int(a.shape[0]) if a.ndim else 0
        width = int(a.nbytes // m) if m else 0
        if not m:
            u8 = np.zeros((0, 0), np.uint8)
        elif donate and not a.dtype.hasobject:
            u8 = a.view(np.uint8).reshape(m, width)
        else:
            u8 = np.frombuffer(a.tobytes(), np.uint8).reshape(m, width)
        return u8, ("chunk", r, _dtype_token(a.dtype), tuple(a.shape[1:]))

    def encode_rows_raw(self, payload):
        """Typed ``(m, k)`` chunk matrix + manifest for the fused
        kernel codec — the bitcast to wire bytes happens *in-kernel*
        (``kernels.reloc_codec.encode_pack``), so no host byte view is
        built at all.  Returns ``None`` when the dtype can't ride a
        jax round trip bit-exactly (float64 under x64-off, object
        dtypes): those payloads take the byte-row path instead."""
        from ..kernels.reloc_codec import jax_safe_dtype

        r, rows = payload
        a = np.ascontiguousarray(np.asarray(rows))
        if a.ndim == 0 or a.shape[0] == 0 or a.size == 0 \
                or not jax_safe_dtype(a.dtype):
            return None
        m = int(a.shape[0])
        return (a.reshape(m, -1),
                ("chunk", r, _dtype_token(a.dtype), tuple(a.shape[1:])))

    def decode_rows(self, rows, manifest):
        """Inverse of :meth:`encode_rows`; ``rows`` may be wider than
        the encoded width (transport padding) and may live on device.
        A device block of words (what the fused codec delivers) decodes
        in-kernel (trim + reinterpret, ``kernels.reloc_codec.
        decode_rows``) and only the typed result crosses to host."""
        _, r, dt, trail = manifest
        dtype = np.dtype(dt)
        m = r.size
        nb = int(dtype.itemsize * np.prod(trail, dtype=np.int64))
        if m == 0:
            return r, np.zeros((0,) + trail, dtype)
        if nb and not isinstance(rows, (np.ndarray, list)):
            import jax

            if isinstance(rows, jax.Array) and rows.dtype == np.uint32:
                from ..kernels import ops

                out = ops.reloc_decode_rows(rows[:m], nbytes=nb,
                                            dtype=dtype)
                return r, np.array(out).reshape((m,) + trail)
        buf = _host_bytes(rows)[:m, :nb]
        arr = np.frombuffer(np.ascontiguousarray(buf).tobytes(),
                            dtype=dtype).reshape((m,) + trail).copy()
        return r, arr


class DistBag(DistCollection):
    """Paper's ``DistBag``: unordered multiset, efficient concurrent
    producers; entries have no identity so only bulk relocation exists."""

    def __init__(self, group: PlaceGroup):
        super().__init__(group)

    def _new_handle(self) -> list:
        return []

    def put(self, place: int, item) -> None:
        if _san._ACTIVE:
            _san.check_mutation(self, "put")
        self.handle(place).append(np.asarray(item))

    def put_batch(self, place: int, items) -> None:
        if _san._ACTIVE:
            _san.check_mutation(self, "put_batch")
        self.handle(place).extend(np.asarray(x) for x in items)

    def local_size(self, place: int) -> int:
        return len(self.handle(place))

    def global_size(self) -> int:
        return sum(len(self.handle(p)) for p in self.group.members)

    def items(self, place: int) -> list[np.ndarray]:
        return list(self.handle(place))

    def clear(self, place: int) -> None:
        if _san._ACTIVE:
            _san.check_mutation(self, "clear")
        self.handle(place).clear()

    def move_at_sync_count(self, place: int, count: int, dest: int, mm) -> None:
        mm.register_bag_move(self, place, count, dest)

    # producer/receiver (paper §4.2 parallelToBag): apply fn to each row
    # of `source` at `place`, collecting non-None results into this bag.
    def collect_from(self, place: int, source: DistArray,
                     fn: Callable[[int, np.ndarray], Any]) -> None:
        out = self.handle(place)
        src = source.handle(place)
        for r in src.ranges():
            arr = src.chunks[r]
            for i in range(r.size):
                produced = fn(r.start + i, arr[i])
                if produced is not None:
                    out.append(np.asarray(produced))

    # teamed gather (paper §4.3): all entries relocate to `root`.
    def team_gather(self, root: int) -> None:
        self.comm.syncs += 1
        moved = 0
        for p in self.group.members:
            if p == root:
                continue
            h = self.handle(p)
            for item in h:
                self.handle(root).append(item)
                moved += int(np.asarray(item).nbytes)
            h.clear()
        self.comm.record(moved, messages=self.group.size() - 1)

    def _extract_count(self, place: int, count: int):
        h = self.handle(place)
        if len(h) < count:
            raise ValueError(f"bag at place {place} holds {len(h)} < {count}")
        taken = h[-count:]
        del h[-count:]
        return taken

    def _insert_payload(self, dest: int, payload) -> None:
        self.handle(dest).extend(payload)

    def _payload_nbytes(self, payload) -> int:
        # per-item dedup (items encode/ship independently)
        return int(sum(_value_nbytes(x, set()) for x in payload)) + 16

    # -- row codec (transport layer) -------------------------------------
    def encode_rows(self, payload):
        """Bag payload (item list, shapes may differ per item) → one
        byte row per item + per-item specs.  ``put`` normalizes items to
        arrays, but a foreign item (inserted through ``_insert_payload``
        or a subclass) still encodes via the pickle fallback rather than
        as an object array whose bytes would be pointers."""
        rows, specs = [], []
        for item in payload:
            row, spec = _encode_value(item)
            rows.append(row)
            specs.append(spec)
        return rows, ("bag", tuple(specs))

    def decode_rows(self, rows, manifest):
        _, specs = manifest
        return [_decode_value(row, spec) for row, spec in zip(rows, specs)]


class DistMap(DistCollection):
    """Paper's ``DistMap<K,V>`` (and via ``multi=True`` ``DistMultiMap``)."""

    def __init__(self, group: PlaceGroup, *, multi: bool = False):
        super().__init__(group)
        self.multi = multi
        # Concurrent callers (the serving tier retires sequences while an
        # async window's phase 1 extracts) opt in to tolerating keys that
        # vanish between registration and extraction; for everyone else a
        # missing key stays a loud error, not silent entry loss.
        self.tolerate_missing_keys = False

    def _new_handle(self) -> dict:
        return {}

    def put(self, place: int, key, value) -> None:
        if _san._ACTIVE:
            _san.check_mutation(self, "put", key)
        h = self.handle(place)
        if self.multi:
            h.setdefault(key, []).append(value)
        else:
            h[key] = value

    def get(self, place: int, key):
        return self.handle(place)[key]

    def keys(self, place: int):
        return list(self.handle(place).keys())

    def local_size(self, place: int) -> int:
        return len(self.handle(place))

    def global_size(self) -> int:
        return sum(len(self.handle(p)) for p in self.group.members)

    def for_each(self, place: int, fn: Callable[[Any, Any], None]) -> None:
        for k, v in list(self.handle(place).items()):
            fn(k, v)

    # -- device bridge (values become device-resident payloads) ----------
    def to_device(self, place: int, keys: Sequence | None = None) -> int:
        """Bridge local values to device residency: every value (an
        array or an arbitrary pytree of arrays) is ``device_put`` and
        stored back in the handle, so subsequent relocation windows ship
        device buffers — the serving tier's KV shards live here.
        Returns the number of bytes now device-resident."""
        import jax

        if _san._ACTIVE:
            _san.check_mutation(self, "to_device")
        h = self.handle(place)
        moved = 0
        for k in (list(h) if keys is None else keys):
            v = h.get(k)
            if v is None:
                continue
            dv = jax.device_put(v)
            h[k] = dv
            moved += sum(_value_nbytes(x)
                         for x in jax.tree_util.tree_leaves(dv))
        return moved

    def from_device(self, place: int, keys: Sequence | None = None) -> int:
        """Inverse bridge: pull device-resident values back to host
        numpy (checkpointing / inspection path).  Returns bytes moved."""
        import jax

        if _san._ACTIVE:
            _san.check_mutation(self, "from_device")
        h = self.handle(place)
        moved = 0
        for k in (list(h) if keys is None else keys):
            v = h.get(k)
            if v is None:
                continue
            hv = jax.tree_util.tree_map(np.asarray, v)
            h[k] = hv
            moved += sum(_value_nbytes(x)
                         for x in jax.tree_util.tree_leaves(hv))
        return moved

    # KeyRelocatable (paper §5.2): relocate by key→destination rule.
    def move_at_sync(self, place: int, rule: Callable[[Any], int], mm) -> None:
        mm.register_key_moves(self, place, rule)

    def relocate(self, dist: RangeDistribution, mm=None) -> None:
        """Paper §4.4: relocate entries to match a (long-key) distribution
        — the contracted-orders dispatch. Teamed: applies to all places."""
        from .relocation import CollectiveMoveManager
        own_mm = mm is None
        if own_mm:
            mm = CollectiveMoveManager(self.group)
        for p in self.group.members:
            self.move_at_sync(p, lambda k: dist.owner_of(int(k)), mm)
        if own_mm:
            mm.sync()

    def _extract_keys(self, place: int, keys):
        h = self.handle(place)
        if not self.tolerate_missing_keys:
            # validate before popping: a missing key raises with the
            # handle untouched, never with earlier keys already removed
            for k in keys:
                if k not in h:
                    raise KeyError(k)
        out = []
        for k in keys:
            try:
                out.append((k, h.pop(k)))
            except KeyError:
                # removed between registration and extraction (e.g. a
                # serving sequence retired while the async window's
                # phase 1 ran) — nothing to relocate for this key
                pass
        return out

    def _insert_payload(self, dest: int, payload) -> None:
        h = self.handle(dest)
        for k, v in payload:
            if self.multi and isinstance(v, list):
                h.setdefault(k, []).extend(v)
            else:
                h[k] = v

    def _payload_nbytes(self, payload) -> int:
        # one `seen` set per VALUE: leaves aliased inside a value's
        # pytree (shared KV pages) count once — the codec ships them
        # once and rebinds them on decode.  Two *values* sharing a
        # buffer still count (and ship) separately: each value is an
        # independent wire row, so counting per value is what keeps the
        # two accounting surfaces (counts matrix vs delivered bytes)
        # equal on every transport.
        total = 16
        for k, v in payload:
            vv = v if isinstance(v, list) else [v]
            seen: set = set()
            total += 8 + sum(_value_nbytes(x, seen) for x in vv)
        return total

    # -- row codec (transport layer) -------------------------------------
    def encode_rows(self, payload):
        """Key/value payload → one byte row per entry + (key, spec)
        manifest.  Values that are pytrees of device buffers (``SeqKV``)
        encode device-side — bitcast + concat, no host bounce — so a
        :class:`~repro.core.transport.DeviceTransport` window moves
        device-resident KV pages through the ``all_to_all`` directly."""
        rows, entries = [], []
        for k, v in payload:
            row, spec = _encode_value(v)
            rows.append(row)
            entries.append((k, spec))
        return rows, ("map", tuple(entries))

    def decode_rows(self, rows, manifest):
        _, entries = manifest
        return [(k, _decode_value(row, spec))
                for row, (k, spec) in zip(rows, entries)]


class DistIdMap(DistMap):
    """Paper's ``DistIdMap``: long keys, tracked distribution."""

    def __init__(self, group: PlaceGroup):
        super().__init__(group, multi=False)
        self._dist = RangeDistribution()

    def put(self, place: int, key: int, value) -> None:
        # the dist assign must not interleave with a background window's
        # update_dist rebuild (serving admits while window N delivers)
        with self._lock:
            super().put(place, int(key), value)
            self._dist.assign(LongRange(int(key), int(key) + 1), place)

    def get_distribution(self) -> RangeDistribution:
        with self._lock:
            return self._dist.copy()

    def update_dist(self) -> None:
        with self._lock:
            new = RangeDistribution()
            local = {p: self.keys(p) for p in self.group.local_places()}
            if self.group.process_backed:
                merged: dict = {}
                for part in self.group.backend.allgather(local):
                    if part is not None:   # dead ranks contribute nothing
                        merged.update(part)
                local = merged
            for p, keys in local.items():
                for k in keys:
                    new.assign(LongRange(k, k + 1), p)
            self._dist = new


def DistMultiMap(group: PlaceGroup) -> DistMap:
    """Paper's ``DistMultiMap``: multiple values per key."""
    return DistMap(group, multi=True)


# ---------------------------------------------------------------------------
# Replication: CachableArray / CachableChunkedList
# ---------------------------------------------------------------------------
class CachableArray(DistCollection):
    """Paper §4.1: owner-updated array replicated on every place.

    ``broadcast(pack, unpack)`` extracts an update object from the
    owner's entries and applies it to every replica — on TPU this is the
    replicated-parameter / serving-weights refresh (a ``broadcast``
    collective from the owner's shard).
    """

    def __init__(self, group: PlaceGroup, values, *, owner: int = 0):
        super().__init__(group)
        self.owner = owner
        self._template = [v for v in values]
        for p in group.members:
            self._handles[p] = [np.copy(np.asarray(v)) for v in values]

    def _new_handle(self):
        return [np.copy(np.asarray(v)) for v in self._template]

    def local(self, place: int) -> list[np.ndarray]:
        return self.handle(place)

    def broadcast(self, pack: Callable[[Any], Any],
                  unpack: Callable[[Any, Any], Any]) -> None:
        self.comm.syncs += 1
        src = self.handle(self.owner)
        updates = [pack(v) for v in src]
        nbytes = sum(int(np.asarray(u).nbytes) for u in updates)
        self.comm.record(nbytes * (self.group.size() - 1),
                         messages=self.group.size() - 1)
        for p in self.group.members:
            h = self.handle(p)
            for i, u in enumerate(updates):
                res = unpack(h[i], u)
                if res is not None:
                    h[i] = np.asarray(res)


class CachableChunkedList(DistArray):
    """Paper §4.9/§4.12: chunked list whose ranges can be *shared*
    (replicated) on all places, with a primitive-typed ``allreduce`` to
    reconcile per-replica contributions (MolDyn force sum — i.e. the
    data-parallel gradient allreduce pattern).
    """

    def __init__(self, group: PlaceGroup):
        super().__init__(group, track=True)
        self.shared_ranges: list[LongRange] = []

    def share(self, place: int, r: LongRange | None = None) -> None:
        """Teamed: the places owning ``r`` replicate it everywhere; places
        calling with ``r=None`` only receive (paper Listing 9)."""
        if r is None:
            return
        rows = self.handle(place).chunks.get(r)
        if rows is None:
            rows = self.handle(place).extract(r)
            self.handle(place).add_chunk(r, rows)
        self.comm.syncs += 1
        self.comm.record(int(rows.nbytes) * (self.group.size() - 1),
                         messages=self.group.size() - 1)
        for p in self.group.members:
            if p == place:
                continue
            self.handle(p).add_chunk(r, np.copy(rows))
        self.shared_ranges.append(r)

    def allreduce(self, pack: Callable[[np.ndarray], np.ndarray],
                  unpack: Callable[[np.ndarray, np.ndarray], np.ndarray],
                  op: str = "sum") -> None:
        """Elementwise allreduce over the replicated ranges. ``pack`` maps
        rows → a float lane matrix; ``unpack`` writes reduced lanes back.
        Mirrors Listing 11 (write/read Double + MPI.SUM)."""
        self.comm.syncs += 1
        reducers = {"sum": np.add.reduce, "max": np.maximum.reduce,
                    "min": np.minimum.reduce}
        red = reducers[op]
        for r in self.shared_ranges:
            lanes = [np.asarray(pack(self.handle(p).chunks[r]))
                     for p in self.group.members]
            reduced = red(np.stack(lanes, 0), axis=0)
            self.comm.record(lanes[0].nbytes * self.group.size(),
                             messages=self.group.size())
            for p in self.group.members:
                out = unpack(self.handle(p).chunks[r], reduced)
                if out is not None:
                    self.handle(p).chunks[r] = np.asarray(out)
