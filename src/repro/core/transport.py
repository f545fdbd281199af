"""Pluggable relocation transports — one data plane for every payload.

The §5.3 two-phase exchange has two halves: *what* moves (the payloads
``CollectiveMoveManager._phase1`` extracts from the collections) and
*how* it moves.  BCL and DASH both get portability by isolating their
containers from the communication backend behind a thin transport
interface; this module does the same for the relocation engine:

* :class:`RelocationTransport` — the protocol.  ``exchange(group,
  counts, payloads)`` takes the phase-1 byte-count matrix plus the
  extracted ``(collection, src, dest, payload)`` tuples and returns the
  payloads *as the destination receives them*, with a per-window
  :class:`TransportStats`.

* :class:`HostTransport` — today's numpy loopback, verbatim: payloads
  pass through by reference (the single-process emulation of the host
  Alltoallv).  Zero copies, zero behavior change — the default.

* :class:`DeviceTransport` — the wire actually rides the device: each
  payload's rows are encoded into fixed-width byte buffers by the
  owning collection's row codec (``encode_rows``/``decode_rows`` —
  ``SeqKV`` pytrees bitcast + concat *on device*, so KV pages never
  bounce through host memory), packed into per-place send buffers under
  the prefix invariant, shipped with **one** jitted masked
  ``all_to_all`` (reusing ``core/spmd_glb._ship_hop``'s cumsum/
  searchsorted pack/compact machinery), and decoded on the receiver
  into bit-identical payloads.

Both backends produce bit-identical final collection state under the
existing pipeline-depth-2 window chaining, evictions, and
admission-time puts (``tests/test_transport.py`` asserts it); the
``reloc_transport`` benchmark row measures the device win on the
hot-shard steal configuration.

A self-destined payload never reaches the wire on either backend — the
counts diagonal stays zero, keeping the two §5.3 accounting surfaces
(``last_counts_matrix.sum() == last_payload_bytes``) in agreement.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Protocol, Sequence, runtime_checkable

import numpy as np

from . import telemetry

__all__ = [
    "RelocationTransport",
    "TransportStats",
    "HostTransport",
    "DeviceTransport",
    "make_transport",
]


@dataclass
class TransportStats:
    """One relocation window's wire accounting, per transport."""

    kind: str = "host"
    payloads: int = 0        # payload tuples that crossed places
    local: int = 0           # self-destined payloads (never on the wire)
    rows: int = 0            # encoded rows exchanged (device path)
    row_bytes: int = 0       # unpadded payload bytes on the wire
    wire_bytes: int = 0      # valid rows × padded class width (row
    #                          padding included; the dense buffers'
    #                          empty capacity slots are not)
    pad_waste_bytes: int = 0  # wire_bytes minus unpadded payload bytes
    #                          actually shipped — the pow2 _width_class
    #                          padding overhead, the number the fused
    #                          codec trajectory is judged against
    buffer_bytes: int = 0    # bytes of the dense send buffers the
    #                          exchanges allocated, empty slots included
    #                          (fused: pairs × slots × W per round;
    #                          masked: places × capacity × W)
    width: int = 0           # widest padded row-width class exchanged
    exchanges: int = 0       # jitted all_to_all dispatches (one per
    #                          row-width class in the window)
    codec_backend: str = ""  # resolved kernels.ops backend the window's
    #                          codec ran on ("xla", "pallas",
    #                          "pallas_interpret"; "" = no codec ran)

    def merge(self, other: "TransportStats") -> "TransportStats":
        """Accumulate ``other`` into self (lifetime totals from
        per-window stats; ``width`` is a high-water mark and
        ``codec_backend`` keeps the most recent window's value)."""
        self.payloads += other.payloads
        self.local += other.local
        self.rows += other.rows
        self.row_bytes += other.row_bytes
        self.wire_bytes += other.wire_bytes
        self.pad_waste_bytes += other.pad_waste_bytes
        self.buffer_bytes += other.buffer_bytes
        self.exchanges += other.exchanges
        self.width = max(self.width, other.width)
        if other.codec_backend:
            self.codec_backend = other.codec_backend
        return self

    def as_dict(self, prefix: str = "") -> dict:
        """Flat ``{name: number}`` view (plus the ``codec_backend``
        string) — the shape both the metrics registry and the bench
        JSON consume."""
        return {
            f"{prefix}payloads": self.payloads,
            f"{prefix}local": self.local,
            f"{prefix}rows": self.rows,
            f"{prefix}row_bytes": self.row_bytes,
            f"{prefix}wire_bytes": self.wire_bytes,
            f"{prefix}pad_waste_bytes": self.pad_waste_bytes,
            f"{prefix}buffer_bytes": self.buffer_bytes,
            f"{prefix}width": self.width,
            f"{prefix}exchanges": self.exchanges,
            f"{prefix}codec_backend": self.codec_backend,
        }

    def publish(self, registry=None) -> None:
        """Snapshot these stats into the metrics registry as
        ``transport.<kind>.*`` counters (and a ``width`` gauge).

        Values are *set*, not incremented, so this is meant for
        cumulative stats (a transport's ``lifetime``) and is how the
        registry-publisher hook works: ``_account_exchange`` registers
        the lifetime stats once and the registry polls them at read
        time — the exchange hot path never pays per-field updates."""
        reg = registry if registry is not None else telemetry.metrics()
        names = _PUBLISH_NAMES.get(self.kind)
        if names is None:
            p = f"transport.{self.kind}."
            names = tuple(p + f for f in (
                "payloads", "local", "rows", "row_bytes", "wire_bytes",
                "pad_waste_bytes", "buffer_bytes", "exchanges", "width"))
            _PUBLISH_NAMES[self.kind] = names
        reg.counter(names[0]).set(self.payloads)
        reg.counter(names[1]).set(self.local)
        reg.counter(names[2]).set(self.rows)
        reg.counter(names[3]).set(self.row_bytes)
        reg.counter(names[4]).set(self.wire_bytes)
        reg.counter(names[5]).set(self.pad_waste_bytes)
        reg.counter(names[6]).set(self.buffer_bytes)
        reg.counter(names[7]).set(self.exchanges)
        reg.gauge(names[8]).set(self.width)


# metric-name tuples per transport kind, built once (publish is invoked
# at registry read time but also directly by tests/benches)
_PUBLISH_NAMES: dict = {}


def _account_exchange(transport, stats: TransportStats, sp) -> None:
    """Shared post-exchange bookkeeping for every backend: fold the
    window stats into the transport's lifetime totals (under its lock),
    stamp this exchange's numbers on the open ``transport.exchange``
    span, and register the lifetime stats as a registry publisher.  One
    implementation — the Device and Distributed backends used to each
    hand-roll the lifetime accumulation."""
    with transport._lifetime_lock:
        transport.lifetime.merge(stats)
    if sp:
        sp.set(payloads=stats.payloads, local=stats.local,
               rows=stats.rows, row_bytes=stats.row_bytes,
               wire_bytes=stats.wire_bytes,
               buffer_bytes=stats.buffer_bytes, width=stats.width,
               exchanges=stats.exchanges)
        telemetry.metrics().add_publisher(
            id(transport), transport.lifetime.publish)


# per-collection-type capability probe for the codec donation fast path
_DONATE_OK: dict[type, bool] = {}


def _encode_rows(col, payload):
    """Call a collection's row codec, passing ``donate=True`` when the
    codec supports it: the transport packs the returned rows into the
    send buffer immediately and never mutates them, so a donating codec
    may hand back zero-copy views of the extracted chunk instead of a
    ``tobytes`` copy.  Probed once per collection type — third-party
    collections without the keyword keep working unchanged."""
    ok = _DONATE_OK.get(type(col))
    if ok is None:
        import inspect

        try:
            ok = "donate" in inspect.signature(col.encode_rows).parameters
        except (TypeError, ValueError):
            ok = False
        _DONATE_OK[type(col)] = ok
    if ok:
        return col.encode_rows(payload, donate=True)
    return col.encode_rows(payload)


@runtime_checkable
class RelocationTransport(Protocol):
    """How extracted payloads cross places (the Alltoallv back end).

    A transport may also declare ``device_plane = True`` to tell the
    GLB's jit-resident steal loop that rows should ride the loop's own
    ``all_to_all`` payload slot (``run_device_steal(ship_rows=True)``)
    instead of materializing host-side by id — so custom device-class
    backends keep steal and migration on one data plane."""

    device_plane: bool = False

    def exchange(self, group, counts: np.ndarray | None,
                 payloads: Sequence[tuple]) -> tuple[list, TransportStats]:
        """Ship phase-1 payloads; return them as delivered (same order
        as ``payloads`` — insertion order is part of determinism).

        ``counts`` is the window's phase-1 place×place *byte*-count
        matrix — informational, for flow control or validation by
        custom backends (rate limiting, chunking a huge window).  The
        built-in backends derive their own row counts from the payloads
        and ignore it."""
        ...


class HostTransport:
    """Today's numpy loopback, extracted verbatim from the move
    manager: within one process the host Alltoallv is reference
    passing — the delivered payload *is* the extracted payload.  The
    object-identity semantics the serving tier relies on (a ``SeqKV``
    mutated in place while in flight still lands fresh) hold only on
    this backend."""

    device_plane = False

    def __init__(self):
        import threading

        self.lifetime = TransportStats(kind="host")
        self._lifetime_lock = threading.Lock()
        # per-instance exchange ordinal: the span's seq attribute, so a
        # timeline orders this transport's windows even across threads
        self._seq = itertools.count()

    def exchange(self, group, counts, payloads):
        with telemetry.span("transport.exchange", kind="host",
                            seq=next(self._seq)) as sp:
            stats = TransportStats(kind="host")
            for _, src, dest, _ in payloads:
                if src == dest:
                    stats.local += 1
                else:
                    stats.payloads += 1
            _account_exchange(self, stats, sp)
        return list(payloads), stats


class DeviceTransport:
    """Payload rows ride jitted masked ``all_to_all`` exchanges.

    A window's payloads are bucketed by *row-width class* (next power
    of two ≥ the payload's widest row, floored at ``pad_multiple``) and
    each class runs one collective — so a window carrying both small
    metadata rows and KV pages pads neither to the other's width.
    Buffer capacity is rounded to a power of two too, so the jit cache
    keys (n, capacity, width) recur across windows of similar traffic
    instead of recompiling per exact row count.

    Delivered payloads are *reconstructions* (bit-identical bytes, new
    objects): alias structure inside a payload is preserved by the
    codec, object identity across the wire is not — exactly like a real
    multi-host deployment.
    """

    device_plane = True

    def __init__(self, *, pad_multiple: int = 8, jit_cache_cap: int = 32):
        import threading

        from ..kernels.reloc_codec import LRUCache

        self.pad_multiple = int(pad_multiple)
        # bounded: long elastic runs change n on every resize, and each
        # (n, S, W) key is a compiled program — the eviction counter
        # (published as transport.device.jit_cache_*) is the thrash
        # signal, the cap the leak stop
        self._fns = LRUCache(jit_cache_cap)
        self.lifetime = TransportStats(kind="device")
        # one shared instance serves many managers' background delivery
        # threads (the README's shared-jit-cache pattern) — the counter
        # read-modify-writes must not interleave across them
        self._lifetime_lock = threading.Lock()
        self._seq = itertools.count()

    # -- the jitted exchange (cached per (n, S, W)) -----------------------
    def _exchange_fn(self, n: int, S: int, W: int):
        key = (n, S, W)
        fn = self._fns.get(key)
        if fn is None:
            import jax
            import jax.numpy as jnp

            from .spmd_glb import _ship_hop

            def transport_masked_all_to_all(buf, ship):
                # prefix invariant: each shard's outgoing rows occupy
                # slots [0, sum(ship[me])) grouped by destination — the
                # same layout _ship_hop's cumsum gathers assume, so the
                # whole exchange is one masked all_to_all, no sort
                me = jax.lax.axis_index("transport")
                count = jnp.sum(ship[me])
                gids = jnp.zeros((S,), jnp.int32)
                nx, _, _ = _ship_hop(buf, gids, count, ship,
                                     axis_name="transport")
                return nx

            # the function's name is the program's name in a profile
            fn = jax.jit(jax.vmap(transport_masked_all_to_all,
                                  axis_name="transport", in_axes=(0, None)))
            self._fns.put(key, fn)
        return fn

    def _fused_exchange_fn(self, n: int, Sp: int, W: int):
        """The fused-codec collective: the kernel-packed send buffer is
        slotted per (src, dest) pair, so the all_to_all needs no mask
        and no prefix bookkeeping — shard s's ``buf[d]`` word stream
        lands verbatim at the receiver's ``recv[d][s]``."""
        key = ("fused", n, Sp, W)
        fn = self._fns.get(key)
        if fn is None:
            import jax

            def transport_all_to_all(buf):
                return jax.lax.all_to_all(buf, "transport", 0, 0,
                                          tiled=False)

            fn = jax.jit(jax.vmap(transport_all_to_all,
                                  axis_name="transport"))
            self._fns.put(key, fn)
        return fn

    def _publish_jit_cache(self, registry=None) -> None:
        reg = registry if registry is not None else telemetry.metrics()
        info = self._fns.info()
        reg.gauge("transport.device.jit_cache_size").set(info["size"])
        reg.gauge("transport.device.jit_cache_cap").set(info["cap"])
        reg.counter("transport.device.jit_cache_hits").set(info["hits"])
        reg.counter("transport.device.jit_cache_misses").set(
            info["misses"])
        reg.counter("transport.device.jit_cache_evictions").set(
            info["evictions"])

    def exchange(self, group, counts, payloads):
        with telemetry.span("transport.exchange", kind="device",
                            seq=next(self._seq)) as sp:
            return self._exchange(group, counts, payloads, sp)

    def _exchange(self, group, counts, payloads, sp):
        import jax

        from ..kernels import ops

        n = group.size()
        place_index = {p: i for i, p in enumerate(group.members)}
        # resolved once per window: the whole window's codec runs on one
        # backend, so fused and composite rows never mix in a bucket
        backend = ops.resolve_backend()
        fused = backend in ("pallas", "pallas_interpret")
        stats = TransportStats(kind="device", codec_backend=backend)

        # staging: encode each payload and bucket it by width class
        with telemetry.span("transport.stage"):
            # encode off-place payloads; self-moves bypass the wire verbatim
            entries: dict[int, dict] = {}   # payload position -> wire entry
            for pos, (col, src, dest, payload) in enumerate(payloads):
                if src == dest:
                    stats.local += 1
                    continue
                if fused:
                    raw_fn = getattr(col, "encode_rows_raw", None)
                    raw = raw_fn(payload) if raw_fn is not None else None
                    if raw is not None:
                        # typed chunk matrix: the encode+pack call turns
                        # it into wire words on device — no host byte
                        # view at all
                        mat, manifest = raw
                        m, k = int(mat.shape[0]), int(mat.shape[1])
                        nb = k * np.dtype(mat.dtype).itemsize
                        entries[pos] = {
                            "pos": pos, "si": place_index[src],
                            "di": place_index[dest], "raw": mat, "m": m,
                            "wmax": nb, "nbytes": m * nb,
                            "manifest": manifest,
                            "dev": isinstance(mat, jax.Array)}
                        stats.payloads += 1
                        stats.rows += m
                        stats.row_bytes += m * nb
                        continue
                rows, manifest = _encode_rows(col, payload)
                if isinstance(rows, np.ndarray) and rows.ndim == 2:
                    # chunk payloads stay one (m, w) matrix end to end: the
                    # pack is a single block copy, never m row assignments
                    e = {"pos": pos, "si": place_index[src],
                         "di": place_index[dest], "mat": rows,
                         "m": int(rows.shape[0]), "wmax": int(rows.shape[1]),
                         "nbytes": int(rows.size), "manifest": manifest,
                         "dev": False}
                else:
                    rows = list(rows)
                    widths = [int(r.size) * np.dtype(r.dtype).itemsize
                              for r in rows]
                    e = {"pos": pos, "si": place_index[src],
                         "di": place_index[dest], "rows": rows,
                         "widths": widths, "m": len(rows),
                         "wmax": max(widths, default=0),
                         "nbytes": int(sum(widths)), "manifest": manifest,
                         "dev": any(isinstance(r, jax.Array) for r in rows)}
                entries[pos] = e
                stats.payloads += 1
                stats.rows += e["m"]
                stats.row_bytes += e["nbytes"]

            delivered = list(payloads)
            # decode zero-row payloads host-side (delivered objects are
            # reconstructions even when nothing crossed the wire); bucket
            # the rest by padded row-width class — one masked all_to_all per
            # class, so small metadata rows (a pickled Sequence) never pad
            # to a KV page's width when both ride one window
            buckets: dict[int, list[dict]] = {}
            for e in entries.values():
                if e["m"] == 0:
                    col, src, dest, _ = payloads[e["pos"]]
                    delivered[e["pos"]] = (col, src, dest,
                                           col.decode_rows([], e["manifest"]))
                    continue
                buckets.setdefault(self._width_class(e["wmax"]), []).append(e)
        for W, bucket in sorted(buckets.items()):
            if fused:
                self._exchange_bucket_fused(n, W, bucket, payloads,
                                            delivered, stats, backend)
            else:
                self._exchange_bucket(n, W, bucket, payloads, delivered,
                                      stats)
        _account_exchange(self, stats, sp)
        if telemetry.enabled():
            telemetry.metrics().add_publisher(
                (id(self), "jit_cache"), self._publish_jit_cache)
        return delivered, stats

    def _width_class(self, w: int) -> int:
        """Next power of two ≥ ``w`` (floored at ``pad_multiple``) — the
        bucket key, so windows of similar payloads hit one jit entry."""
        w = max(int(w), self.pad_multiple)
        return 1 << (w - 1).bit_length()

    def _exchange_bucket(self, n, W, bucket, payloads, delivered, stats):
        """One masked ``all_to_all`` over the entries of one row-width
        class; decodes straight into ``delivered``."""
        with telemetry.span("transport.stage"):
            per_src: list[list[dict]] = [[] for _ in range(n)]
            # each sender's prefix is grouped by destination (stable within
            # a destination: registration order) — the receive side then
            # reads contiguous blocks per (src, dest) pair
            for e in bucket:
                per_src[e["si"]].append(e)
            for si in range(n):
                per_src[si].sort(key=lambda e: e["di"])
            ship = np.zeros((n, n), np.int32)
            for e in bucket:
                ship[e["si"], e["di"]] += e["m"]
            # capacity covers BOTH sides of the exchange — the busiest
            # sender's outgoing total and the busiest receiver's incoming
            # total (_ship_hop's receive prefix lands in the same S slots;
            # fan-in past S would silently drop rows) — rounded to the next
            # power of two so successive windows of similar traffic reuse
            # one (n, S, W) jit specialization instead of recompiling per
            # exact row count
            S = int(max(ship.sum(axis=1).max(), ship.sum(axis=0).max(), 1))
            S = 1 << (S - 1).bit_length()
            buf = self._pack(per_src, n, S, W,
                             device=any(e["dev"] for e in bucket))

        recv = self._exchange_fn(n, S, W)(buf, ship)
        stats.exchanges += 1
        stats.width = max(stats.width, W)
        wire = int(ship.sum()) * W
        stats.wire_bytes += wire
        stats.pad_waste_bytes += wire - sum(e["nbytes"] for e in bucket)
        stats.buffer_bytes += n * S * W

        # receive layout: shard d's prefix holds, for src 0..n-1, the
        # ship[src, d] rows that src packed for d, in src's order.
        # Host-decoded entries copy only their own row block to host —
        # never the whole (n, S, W) padded capacity, which would drag
        # the device-resident KV rows of a mixed bucket along with it
        with telemetry.span("transport.unstage"):
            offsets = np.zeros(n, np.int64)
            for si in range(n):
                for e in per_src[si]:
                    di, m = e["di"], e["m"]
                    lo = int(offsets[di])
                    block = recv[di, lo:lo + m]
                    if not e["dev"]:
                        block = np.asarray(block)
                    offsets[di] += m
                    rows = block if "mat" in e \
                        else [block[i] for i in range(m)]
                    col, src, dest, _ = payloads[e["pos"]]
                    delivered[e["pos"]] = (
                        col, src, dest, col.decode_rows(rows, e["manifest"]))

    def _pack(self, per_src, n, S, W, *, device):
        """(n, S, W) uint8 send buffer under the prefix invariant; built
        with jnp when any row is a device buffer (KV pages never touch
        host memory on the way in).  Chunk matrices land as one block
        copy each; only genuinely ragged per-row payloads loop."""
        if not device:
            buf = np.zeros((n, S, W), np.uint8)
            for si in range(n):
                off = 0
                for e in per_src[si]:
                    if "mat" in e:
                        buf[si, off:off + e["m"], :e["wmax"]] = e["mat"]
                        off += e["m"]
                    else:
                        for r, w in zip(e["rows"], e["widths"]):
                            buf[si, off, :w] = np.asarray(r, np.uint8)
                            off += 1
            return buf
        import jax.numpy as jnp

        shards = []
        for si in range(n):
            blocks = []
            for e in per_src[si]:
                if "mat" in e:
                    blk = jnp.asarray(e["mat"], jnp.uint8)
                    if e["wmax"] < W:
                        blk = jnp.pad(blk, ((0, 0), (0, W - e["wmax"])))
                    blocks.append(blk)
                    continue
                for r, w in zip(e["rows"], e["widths"]):
                    r = _device_bytes(r)
                    if w < W:
                        r = jnp.concatenate(
                            [r, jnp.zeros((W - w,), jnp.uint8)])
                    blocks.append(r[None, :])
            m = sum(int(b.shape[0]) for b in blocks)
            blocks.append(jnp.zeros((S - m, W), jnp.uint8))
            shards.append(jnp.concatenate(blocks))
        return jnp.stack(shards)

    # -- the fused-kernel window path (backend "pallas"/"pallas_interpret")
    def _exchange_bucket_fused(self, n, W, bucket, payloads, delivered,
                               stats, backend):
        """Fused-codec kernel + unmasked ``all_to_all`` over the entries
        of one row-width class.

        The send buffer is slotted *per (src, dest) pair* — capacity is
        the pow2 of the busiest pair, every pair owns its own block — so
        the whole encode → permute → pad chain is a single
        ``pallas_call``, the collective needs no mask, receiver blocks
        are contiguous slices, and fan-in can never overflow a shared
        prefix.  Delivered bytes are bit-identical to the composite
        path: entries pack in registration order within each pair, the
        same order ``_exchange_bucket`` produces.

        A dense ``(pairs, slots, W)`` buffer of KV-sized rows can pass
        the device's memory (4 replicas × 4 slots of 64 MiB rows is
        4 GiB, twice over once the exchange copies it), so the pair
        slots run in rounds of at most :data:`_EXCHANGE_BYTES` of
        buffer each; a round's received blocks are sliced out before
        the next round starts."""
        import jax
        import jax.numpy as jnp

        from ..kernels import ops
        from ..kernels.reloc_codec import to_words
        from .collections import _host_bytes, _row_words

        with telemetry.span("transport.stage"):
            ship = np.zeros((n, n), np.int32)
            for e in bucket:
                ship[e["si"], e["di"]] += e["m"]
            Sp = 1 << (int(ship.max()) - 1).bit_length()
            pairs = n * n
            cap = max(_EXCHANGE_BYTES // (pairs * W), 1)
            Sp = min(Sp, 1 << (cap.bit_length() - 1))
            rounds = -(-int(ship.max()) // Sp)
            Sv = rounds * Sp                  # slots per pair over all rounds

            # slot assignment: each entry's rows land at [p0, p0+m) inside
            # its pair's block, accumulated in registration order
            fill = np.zeros((n, n), np.int64)
            for e in bucket:
                e["p0"] = int(fill[e["si"], e["di"]])
                fill[e["si"], e["di"]] += e["m"]

            wid_tab = np.zeros((pairs, Sv), np.int32)
            src_tab = np.zeros((pairs, Sv), np.int32)   # row index / offset
            raw_keys = {(str(np.dtype(e["raw"].dtype)),
                         int(e["raw"].shape[1]))
                        for e in bucket if "raw" in e}
            if len(raw_keys) == 1 and all("raw" in e for e in bucket):
                # homogeneous typed bucket (the chunk-steal hot path): the
                # encode+pack kernel reads straight off the concatenated
                # chunk matrices
                mats, base = [], 0
                for e in bucket:
                    pr, s0 = e["si"] * n + e["di"], e["p0"]
                    src_tab[pr, s0:s0 + e["m"]] = np.arange(
                        base, base + e["m"])
                    wid_tab[pr, s0:s0 + e["m"]] = e["wmax"]
                    mats.append(e["raw"])
                    base += e["m"]
                if any(isinstance(x, jax.Array) for x in mats):
                    src = jnp.concatenate([jnp.asarray(x) for x in mats])
                else:
                    src = np.concatenate(mats)
                pack = ops.reloc_encode_pack
            else:
                # mixed bucket: every entry contributes word rows to one
                # arena; a single pack kernel gathers them into slots
                pieces, dev, base = [], False, 0
                for e in bucket:
                    pr, s0 = e["si"] * n + e["di"], e["p0"]
                    if "rows" in e:
                        for j, (r, w) in enumerate(zip(e["rows"],
                                                       e["widths"])):
                            r = _row_words(r)
                            src_tab[pr, s0 + j] = base
                            wid_tab[pr, s0 + j] = w
                            dev = dev or isinstance(r, jax.Array)
                            pieces.append(r)
                            base += int(r.shape[0])
                        continue
                    if "mat" in e:
                        wm = e["mat"]
                        wm = np.pad(wm, ((0, 0), (0, (-wm.shape[1]) % 4)))
                        wm = np.ascontiguousarray(wm).view(np.uint32)
                    else:
                        wm = to_words(e["raw"])
                    m, wq = e["m"], int(wm.shape[1])
                    src_tab[pr, s0:s0 + m] = base + wq * np.arange(m)
                    wid_tab[pr, s0:s0 + m] = 4 * wq
                    dev = dev or isinstance(wm, jax.Array)
                    pieces.append(wm.reshape(-1))
                    base += m * wq
                if dev:
                    src = jnp.concatenate([jnp.asarray(p) for p in pieces])
                else:
                    src = np.concatenate(pieces)
                del pieces
                pack = ops.reloc_pack_rows

        # recv[di, si] is exactly what si packed for di; each entry's
        # rows are the contiguous slot range it claimed above.  Typed
        # (raw) entries keep their block on device — the collection's
        # decode fast path trims + reinterprets it in-kernel
        blocks: dict[int, list] = {id(e): [] for e in bucket}
        for k in range(rounds):
            lo = k * Sp
            buf = pack(src, src_tab[:, lo:lo + Sp].reshape(-1),
                       wid_tab[:, lo:lo + Sp].reshape(-1), pairs=pairs,
                       slots=Sp, width=W, impl=backend)
            recv = self._fused_exchange_fn(n, Sp, W)(
                buf.reshape((n, n) + buf.shape[1:]))
            del buf
            with telemetry.span("transport.unstage"):
                for e in bucket:
                    a = max(e["p0"], lo)
                    b = min(e["p0"] + e["m"], lo + Sp)
                    if a < b:
                        blocks[id(e)].append(_slot_rows(
                            recv[e["di"], e["si"]], a - lo, b - a, W))
            del recv
        stats.exchanges += rounds
        stats.width = max(stats.width, W)
        wire = int(ship.sum()) * W
        stats.wire_bytes += wire
        stats.pad_waste_bytes += wire - sum(e["nbytes"] for e in bucket)
        stats.buffer_bytes += pairs * Sp * W * rounds

        with telemetry.span("transport.unstage"):
            for e in bucket:
                parts = blocks[id(e)]
                block = parts[0] if len(parts) == 1 \
                    else jnp.concatenate(parts)
                if not (e["dev"] or "raw" in e):
                    block = _host_bytes(block)
                rows = block if ("mat" in e or "raw" in e) \
                    else [block[i] for i in range(e["m"])]
                col, src_, dest, _ = payloads[e["pos"]]
                delivered[e["pos"]] = (
                    col, src_, dest, col.decode_rows(rows, e["manifest"]))


def _slot_rows(stream, r0: int, m: int, width: int):
    """Rows ``[r0, r0 + m)`` of one pair's ``(chunks, 1, 128)`` word
    stream as an ``(m, width // 4)`` word block — only the chunks that
    hold them are touched."""
    wq = width // 4
    a, b = r0 * wq, (r0 + m) * wq
    c0, c1 = a // 128, -(-b // 128)
    words = stream[c0:c1].reshape(-1)
    return words[a - c0 * 128:b - c0 * 128].reshape(m, wq)


def _device_bytes(row):
    """A device row (uint32 words or bytes) as a 1-D device uint8 view —
    the composite path's byte buffers (an XLA bitcast; on a TPU this
    view is padded many times over, which is why the fused path stays
    in words)."""
    import jax
    import jax.numpy as jnp

    row = jnp.asarray(row)
    if row.dtype == jnp.uint8:
        return row
    return jax.lax.bitcast_convert_type(row, jnp.uint8).reshape(-1)


# device bytes one fused exchange round may hold in its send buffer
_EXCHANGE_BYTES = 1 << 30


def make_transport(spec: Any) -> RelocationTransport:
    """``None``/``"host"`` → :class:`HostTransport`, ``"device"`` →
    :class:`DeviceTransport`, ``"distributed"`` → the multi-process
    :class:`~repro.core.distributed.DistributedTransport` (binds to the
    launching process backend, degrades to the host loopback in a
    world-size-1 run); an instance passes through (shared jit caches
    across managers/windows)."""
    if spec is None or spec == "host":
        return HostTransport()
    if spec == "device":
        return DeviceTransport()
    if spec == "distributed":
        from .distributed import DistributedTransport

        return DistributedTransport()
    if isinstance(spec, str):
        raise ValueError(f"unknown transport {spec!r} "
                         "(expected 'host', 'device' or 'distributed')")
    # fail at config time, not on a background delivery thread: the
    # instance must implement the protocol (a bare class — an easy
    # typo — is rejected too)
    if isinstance(spec, type) \
            or not callable(getattr(spec, "exchange", None)):
        raise TypeError(
            f"transport {spec!r} does not implement RelocationTransport "
            "(pass an instance with an exchange() method)")
    return spec
