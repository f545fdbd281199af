"""Pluggable relocation transports (ISSUE 5): HostTransport and
DeviceTransport must produce bit-identical final collection state —
entries, tracked distributions, comm-stats byte counts — across
``sync_async`` depth-1 and depth-2 window chains, including an eviction
drain mid-chain and admission-time puts; plus the row-codec round-trip
property and the alias-aware byte accounting."""
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core import (CollectiveMoveManager, DeviceTransport, DistArray,
                        DistBag, DistIdMap, DistMap, HostTransport,
                        LongRange, PlaceGroup, make_transport)
from repro.core.collections import _decode_value, _encode_value, _value_nbytes


def pad(row, extra=5):
    """Transports deliver rows padded to the window's max width — decode
    must ignore the tail."""
    row = np.asarray(row, np.uint8)
    return np.concatenate([row, np.zeros(extra, np.uint8)])


# ---------------------------------------------------------------------------
# row codecs
# ---------------------------------------------------------------------------
class TestRowCodecs:
    def test_dist_array_chunk_roundtrip_dtypes(self):
        g = PlaceGroup(2)
        col = DistArray(g, track=False)
        for dtype in (np.float64, np.float32, np.int32, np.int8, np.bool_):
            rows = (np.arange(12).reshape(6, 2) % 2).astype(dtype)
            payload = (LongRange(3, 9), rows)
            u8, manifest = col.encode_rows(payload)
            assert u8.dtype == np.uint8 and u8.shape[0] == 6
            padded = np.concatenate(
                [u8, np.zeros((6, 3), np.uint8)], axis=1)
            r, back = col.decode_rows(padded, manifest)
            assert r == LongRange(3, 9)
            assert back.dtype == rows.dtype and np.array_equal(back, rows)

    def test_extension_dtypes_roundtrip(self):
        # ml_dtypes extension dtypes stringify as raw void ('<V2') via
        # .str — the manifest must spell them by name or host bf16 KV
        # pages would silently decode as V2
        ml_dtypes = pytest.importorskip("ml_dtypes")
        bf16 = np.dtype(ml_dtypes.bfloat16)
        a = (np.arange(6) / 4).astype(bf16)
        row, spec = _encode_value(a)
        back = _decode_value(pad(row), spec)
        assert back.dtype == bf16
        assert np.array_equal(back.astype(np.float32),
                              a.astype(np.float32))
        col = DistArray(PlaceGroup(2), track=False)
        rows = (np.arange(8).reshape(4, 2) / 4).astype(bf16)
        u8, manifest = col.encode_rows((LongRange(0, 4), rows))
        _, back = col.decode_rows(u8, manifest)
        assert back.dtype == bf16
        assert np.array_equal(back.astype(np.float32),
                              rows.astype(np.float32))

    def test_numpy_scalars_stay_scalars(self):
        # host loopback delivers the original np.float64; the codec
        # must not degrade it to a 0-d ndarray (receivers hash/compare)
        for val in (np.float64(3.5), np.int32(-7), np.bool_(True)):
            row, spec = _encode_value(val)
            back = _decode_value(pad(row), spec)
            assert type(back) is type(val) and back == val
        # scalar leaves inside a pytree round-trip as scalars too
        tree = {"s": np.float32(2.25), "a": np.ones(2)}
        row, spec = _encode_value(tree)
        back = _decode_value(pad(row), spec)
        assert type(back["s"]) is np.float32 and back["s"] == tree["s"]
        assert np.array_equal(back["a"], tree["a"])

    def test_dist_array_scalar_rows(self):
        g = PlaceGroup(2)
        col = DistArray(g, track=False)
        rows = np.arange(5, dtype=np.float64)
        u8, manifest = col.encode_rows((LongRange(0, 5), rows))
        _, back = col.decode_rows(u8, manifest)
        assert np.array_equal(back, rows) and back.dtype == rows.dtype

    def test_map_value_kinds_roundtrip(self):
        # plain array / pytree (dict+list) / arbitrary object (pickle)
        vals = {
            1: np.arange(6, dtype=np.int16).reshape(2, 3),
            2: {"a": np.ones(3, np.float32), "b": [np.zeros(2, np.int64)]},
            3: ("a plain tuple of", 42, "objects"),
        }
        g = PlaceGroup(2)
        m = DistMap(g)
        payload = list(vals.items())
        rows, manifest = m.encode_rows(payload)
        back = m.decode_rows([pad(r) for r in rows], manifest)
        assert [k for k, _ in back] == [1, 2, 3]
        got = dict(back)
        assert np.array_equal(got[1], vals[1]) and got[1].dtype == np.int16
        assert np.array_equal(got[2]["a"], vals[2]["a"])
        assert isinstance(got[2]["b"], list)
        assert np.array_equal(got[2]["b"][0], vals[2]["b"][0])
        assert got[3] == vals[3]

    def test_device_pytree_roundtrip_stays_on_device(self):
        import jax
        from repro.serving.cache import SeqKV

        state = {"k": jax.device_put(
                     np.arange(8, dtype=np.float32).reshape(2, 4)),
                 "flags": jax.device_put(np.array([True, False]))}
        kv = SeqKV(state, jax.device_put(np.full((1, 1), 7, np.int32)))
        row, spec = _encode_value(kv)
        assert isinstance(row, jax.Array)   # encoded device-side
        back = _decode_value(row, spec)
        assert isinstance(back, SeqKV) and back.on_device()
        for a, b in zip(jax.tree_util.tree_leaves(kv),
                        jax.tree_util.tree_leaves(back)):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_aliased_leaves_encode_once_and_rebind(self):
        import jax
        from repro.serving.cache import SeqKV

        page = jax.device_put(np.arange(16, dtype=np.float32))
        kv = SeqKV({"k": page, "v": page},
                   jax.device_put(np.zeros((1, 1), np.int32)))
        row, spec = _encode_value(kv)
        # the shared page crosses the wire once (a device row is words)
        assert row.dtype == np.uint32
        assert int(row.shape[0]) * 4 == page.nbytes + 4
        back = _decode_value(row, spec)
        assert back.state["k"] is back.state["v"]

    def test_bag_roundtrip_mixed_shapes(self):
        g = PlaceGroup(2)
        bag = DistBag(g)
        payload = [np.arange(3, dtype=np.float64),
                   np.ones((2, 2), np.int32)]
        rows, manifest = bag.encode_rows(payload)
        back = bag.decode_rows([pad(r) for r in rows], manifest)
        assert all(np.array_equal(a, b) and a.dtype == b.dtype
                   for a, b in zip(payload, back))

    def test_object_values_fall_back_to_pickle(self):
        # np.asarray of a tuple/dict yields an object array whose raw
        # bytes are pointers — the codec must pickle those whole, never
        # ship their bytes
        obj_arr = np.asarray([("tup", 1), None], dtype=object)
        row, spec = _encode_value(obj_arr)
        assert spec[0] == "pkl"
        back = _decode_value(pad(row), spec)
        assert back.dtype == object and back[0] == ("tup", 1)
        # object leaves inside a pytree force whole-value pickling too
        tree = {"a": np.ones(2), "b": np.asarray(dict(k=2), dtype=object)}
        row, spec = _encode_value(tree)
        assert spec[0] == "pkl"
        back = _decode_value(pad(row), spec)
        assert np.array_equal(back["a"], tree["a"])
        assert back["b"].item() == {"k": 2}

    def test_bag_with_foreign_items_crosses_device_wire(self):
        g = PlaceGroup(2)
        bag = DistBag(g)
        # bypass put()'s asarray normalization (as _insert_payload or a
        # subclass can): host and device transports must still agree
        bag.handle(0).extend([("tup", 1), {"k": 2}, np.arange(3.0)])
        mm = CollectiveMoveManager(g, transport="device")
        bag.move_at_sync_count(0, 3, 1, mm)
        mm.sync()
        items = bag.items(1)
        assert ("tup", 1) in items and {"k": 2} in items
        assert any(isinstance(x, np.ndarray)
                   and np.array_equal(x, np.arange(3.0)) for x in items)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 12), width=st.integers(1, 9),
       dt=st.integers(0, 3), extra=st.integers(0, 16))
def test_property_chunk_codec_roundtrip(m, width, dt, extra):
    """Any chunk payload survives encode → pad → decode bit-exactly."""
    dtype = [np.float64, np.float32, np.int16, np.uint8][dt]
    rng = np.random.default_rng(m * 131 + width * 7 + dt)
    rows = (rng.integers(-1000, 1000, (m, width)) / 7).astype(dtype)
    col = DistArray(PlaceGroup(2), track=False)
    u8, manifest = col.encode_rows((LongRange(0, m), rows))
    padded = np.concatenate([u8, np.zeros((m, extra), np.uint8)], axis=1)
    _, back = col.decode_rows(padded, manifest)
    assert back.dtype == rows.dtype
    assert np.array_equal(back, rows)


# ---------------------------------------------------------------------------
# alias-aware byte accounting (satellite)
# ---------------------------------------------------------------------------
class TestNbytesDedup:
    def test_shared_page_seqkv_counts_once(self):
        import jax
        from repro.serving.cache import SeqKV

        page = jax.device_put(np.zeros((4, 8), np.float32))   # 128 B
        tok = jax.device_put(np.zeros((1, 1), np.int32))      # 4 B
        shared = SeqKV({"k": page, "v": page}, tok)
        distinct = SeqKV({"k": page,
                          "v": jax.device_put(np.zeros((4, 8), np.float32))},
                         tok)
        assert shared.nbytes == 128 + 4
        assert distinct.nbytes == 2 * 128 + 4

    def test_payload_nbytes_dedupes_within_each_value(self):
        import jax
        from repro.serving.cache import SeqKV

        g = PlaceGroup(2)
        m = DistIdMap(g)
        page = jax.device_put(np.zeros((4, 8), np.float32))
        mk = lambda: SeqKV({"k": page, "v": page},  # noqa: E731
                           jax.device_put(np.zeros((1, 1), np.int32)))
        payload = [(0, mk()), (1, mk())]
        # 16 header + per entry: 8 key + 4 token + the page ONCE per
        # value (intra-value aliases are one wire buffer; each VALUE is
        # an independent wire row, so cross-value sharing ships twice
        # and must count twice — that keeps counts.sum() ==
        # last_payload_bytes on every transport)
        assert m._payload_nbytes(payload) == 16 + 2 * (8 + 4 + 128)

    def test_accounting_surfaces_agree_with_cross_value_alias(self):
        # same buffer under two keys: both transports must publish
        # identical counts matrices AND identical delivered bytes, with
        # counts.sum() == last_payload_bytes on each
        page = np.arange(64, dtype=np.float64)
        stats = []
        for transport in ("host", "device"):
            g = PlaceGroup(2)
            m = DistMap(g)
            for p in g.members:
                m.handle(p)
            m.put(0, "a", page)
            m.put(0, "b", page)
            mm = CollectiveMoveManager(g, transport=transport)
            m.move_at_sync(0, lambda k: 1, mm)
            mm.sync()
            assert int(mm.last_counts_matrix.sum()) \
                == mm.last_payload_bytes, transport
            stats.append((mm.last_counts_matrix.tobytes(),
                          mm.last_payload_bytes, m.comm.bytes_moved))
        assert stats[0] == stats[1]

    def test_plain_values_unchanged(self):
        g = PlaceGroup(2)
        m = DistMap(g)
        payload = [("a", np.zeros(4, np.float64))]
        assert m._payload_nbytes(payload) == 16 + 8 + 32
        assert _value_nbytes(np.zeros(3, np.int32)) == 12


# ---------------------------------------------------------------------------
# window-level parity: Host vs Device transport, bit-identical state
# ---------------------------------------------------------------------------
def _snapshot(cols, mms):
    """Full observable state: entries (bytes + dtypes), tracked
    distributions, comm byte counts, manager accounting."""
    snap = []
    for col in cols:
        members = col.group.members
        if isinstance(col, DistArray):
            per_place = []
            for p in members:
                rows, idx = col.to_local_matrix(p)
                per_place.append((col.ranges(p), idx.tolist(),
                                  np.asarray(rows).tobytes(),
                                  str(np.asarray(rows).dtype)))
            snap.append(("array", per_place,
                         col.get_distribution().items() if col.track
                         else None,
                         col.comm.bytes_moved, col.comm.messages))
        else:
            per_place = []
            for p in members:
                entries = []
                for k in sorted(col.keys(p)):
                    v = col.get(p, k)
                    import jax
                    leaves = jax.tree_util.tree_leaves(v)
                    if leaves and all(
                            hasattr(x, "dtype") for x in leaves):
                        entries.append((k, tuple(
                            (str(x.dtype), tuple(x.shape),
                             np.asarray(x).tobytes()) for x in leaves)))
                    else:
                        entries.append((k, repr(v)))
                per_place.append(entries)
            dist = col.get_distribution().items() \
                if isinstance(col, DistIdMap) else None
            snap.append(("map", per_place, dist,
                         col.comm.bytes_moved, col.comm.messages))
    for mm in mms:
        snap.append(("mm", mm.syncs, mm.last_payload_bytes,
                     mm.last_counts_matrix.tobytes()
                     if mm.last_counts_matrix is not None else None))
    return snap


def _drive_windows(transport, depth):
    """A deterministic multi-window scenario over three collections:
    range moves, count moves, key-rule moves with device pytree + pickle
    values, admission-time puts between windows, and an eviction drain
    mid-chain — the shapes the elastic serving tier produces."""
    import jax
    from repro.serving.cache import SeqKV, Sequence

    g = PlaceGroup(4)
    col = DistArray(g, track=True)
    col.add_chunk(0, LongRange(0, 60),
                  np.arange(120, dtype=np.float64).reshape(60, 2))
    for p in g.members:
        col.handle(p)
    seqs = DistIdMap(g)
    kv = DistIdMap(g)
    for p in g.members:
        seqs.handle(p)
        kv.handle(p)

    def admit(k, place):
        seqs.put(place, k, Sequence(k, prompt_len=4 + k))
        page = jax.device_put(np.full((2, 4), k, np.float32))
        kv.put(place, k, SeqKV({"k": page, "v": page},
                               jax.device_put(np.full((1, 1), k, np.int32))))

    for k in range(12):
        admit(k, 0)

    mm = CollectiveMoveManager(g, transport=transport)
    # window 1: ranges + keyed pairs spread off the hot place
    col.move_range_at_sync(LongRange(0, 15), 1, mm)
    col.move_at_sync_count(0, 10, 2, mm)
    rule1 = lambda k: k % 4  # noqa: E731
    seqs.move_at_sync(0, rule1, mm)
    kv.move_at_sync(0, rule1, mm)
    h1 = mm.sync_async(update_dists=(col, seqs, kv), depth=depth)
    # admission-time puts while window 1 is (possibly) in flight — on a
    # chained manager the next window's extraction sees them
    if depth == 1:
        h1.finish()
    for k in range(12, 16):
        admit(k, 3)
    # window 2: an eviction mid-chain — place 3 dies, every entry drains
    # to the survivors through the same manager (the rehome path).
    # register_drain enumerates the victim's keys at *registration*
    # time, so — like the driver's _evict, which settles the in-flight
    # window before re-homing — wait for window 1's delivery first
    # (depth=2: it has been running in the background; the commit stays
    # deferred, so the chain is still live)
    h1.wait_delivered()
    mm.register_drain(col, 3, (0, 1, 2))
    mm.register_drain(seqs, 3, (0, 1, 2))
    mm.register_drain(kv, 3, (0, 1, 2))
    h2 = mm.sync_async(update_dists=(col, seqs, kv), depth=depth)
    if depth == 1:
        h2.finish()
    # window 3: keyed moves again (post-eviction redistribution)
    rule3 = lambda k: (k * 7) % 3  # noqa: E731
    seqs.move_at_sync(1, rule3, mm)
    kv.move_at_sync(1, rule3, mm)
    col.move_at_sync_count(2, 5, 1, mm)
    mm.sync_async(update_dists=(col, seqs, kv), depth=depth)
    mm.drain()
    assert col.global_size() == 60
    assert seqs.global_size() == 16 and kv.global_size() == 16
    return _snapshot((col, seqs, kv), (mm,))


class TestTransportParity:
    @pytest.mark.parametrize("depth", [1, 2])
    def test_host_device_bitwise_parity(self, depth):
        host = _drive_windows(HostTransport(), depth)
        device = _drive_windows(DeviceTransport(), depth)
        assert host == device

    def test_depth1_matches_depth2_on_device(self):
        assert _drive_windows(DeviceTransport(), 1) \
            == _drive_windows(DeviceTransport(), 2)

    def test_device_window_reports_wire_stats(self):
        g = PlaceGroup(3)
        col = DistArray(g, track=True)
        col.add_chunk(0, LongRange(0, 9),
                      np.arange(9, dtype=np.float32)[:, None])
        for p in g.members:
            col.handle(p)
        mm = CollectiveMoveManager(g, transport="device")
        col.move_at_sync_count(0, 6, 1, mm)
        mm.sync()
        st_ = mm.last_transport_stats
        assert st_.kind == "device" and st_.exchanges == 1
        assert st_.rows == 6 and st_.row_bytes == 6 * 4
        assert st_.wire_bytes >= st_.row_bytes
        # host windows report pass-through stats
        mm2 = CollectiveMoveManager(g)
        col.move_at_sync_count(1, 2, 2, mm2)
        mm2.sync()
        assert mm2.last_transport_stats.kind == "host"
        assert mm2.last_transport_stats.payloads == 1

    def test_self_moves_bypass_the_wire(self):
        g = PlaceGroup(2)
        m = DistIdMap(g)
        for p in g.members:
            m.handle(p)
        for k in range(4):
            m.put(0, k, np.full(3, k, np.float32))
        mm = CollectiveMoveManager(g, transport="device")
        m.move_at_sync(0, lambda k: 0 if k < 3 else 1, mm)
        mm.sync()
        st_ = mm.last_transport_stats
        assert st_.rows == 1   # only key 3 crossed
        assert sorted(m.keys(0)) == [0, 1, 2] and m.keys(1) == [3]
        assert int(mm.last_counts_matrix.sum()) == mm.last_payload_bytes

    def test_width_classes_exchange_separately(self):
        # seqs-style small rows + kv-style big rows in ONE window: each
        # width class runs its own collective, so the small rows never
        # pad to the big rows' width
        import jax
        from repro.serving.cache import SeqKV

        g = PlaceGroup(2)
        small = DistIdMap(g)
        big = DistIdMap(g)
        for p in g.members:
            small.handle(p)
            big.handle(p)
        for k in range(3):
            small.put(0, k, np.full(2, k, np.float32))        # 8 B rows
            big.put(0, k, SeqKV(
                {"pg": jax.device_put(np.full((64, 8), k, np.float32))},
                jax.device_put(np.zeros((1, 1), np.int32))))  # 2052 B rows
        mm = CollectiveMoveManager(g, transport="device")
        small.move_at_sync(0, lambda k: 1, mm)
        big.move_at_sync(0, lambda k: 1, mm)
        mm.sync()
        st_ = mm.last_transport_stats
        assert st_.exchanges == 2          # one per width class
        # wire footprint stays near the real bytes: the small rows cost
        # their own class's width, not the KV class's
        assert st_.wire_bytes < 2 * st_.row_bytes + 3 * st_.width
        assert small.keys(1) == [0, 1, 2] and big.global_size() == 3
        assert all(big.get(1, k).on_device() for k in range(3))

    def test_fan_in_exceeding_any_senders_outgoing_total(self):
        # 3 senders × 8 entries all converge on place 0: the receiver's
        # incoming total (24) exceeds every sender's outgoing total (8),
        # so the exchange capacity must be sized by BOTH sides
        g = PlaceGroup(4)
        m = DistMap(g)
        for p in g.members:
            m.handle(p)
        for src in (1, 2, 3):
            for j in range(8):
                m.put(src, f"{src}-{j}", np.full(4, src * 10 + j,
                                                 np.float32))
        mm = CollectiveMoveManager(g, transport="device")
        for src in (1, 2, 3):
            m.move_at_sync(src, lambda k: 0, mm)
        mm.sync()
        assert m.local_size(0) == 24
        for src in (1, 2, 3):
            assert m.local_size(src) == 0
            for j in range(8):
                assert np.array_equal(m.get(0, f"{src}-{j}"),
                                      np.full(4, src * 10 + j, np.float32))

    def test_reattached_workload_follows_new_config(self):
        # a transport a PREVIOUS balancer injected is not user-supplied:
        # a second balancer with an explicit config re-resolves it
        from repro.core import (DistArrayWorkload, GLBConfig,
                                GlobalLoadBalancer, HostTransport)

        g = PlaceGroup(2)
        col = DistArray(g, track=True)
        col.add_chunk(0, LongRange(0, 4),
                      np.arange(4, dtype=np.float64)[:, None])
        w = DistArrayWorkload(col)
        glb1 = GlobalLoadBalancer(g, w, GLBConfig())
        assert isinstance(glb1.transport, HostTransport)
        glb2 = GlobalLoadBalancer(g, w, GLBConfig(transport="device"))
        assert isinstance(glb2.transport, DeviceTransport)
        assert w.transport is glb2.transport
        # ...but a transport the user assigns DIRECTLY (a different
        # object than the injected one) is adopted, not clobbered
        mine = DeviceTransport()
        w.transport = mine
        glb3 = GlobalLoadBalancer(g, w, GLBConfig())
        assert glb3.transport is mine and w.transport is mine

    def test_all_local_window_still_accounts_lifetime(self):
        g = PlaceGroup(2)
        col = DistArray(g, track=False)
        col.add_chunk(0, LongRange(0, 4),
                      np.arange(4, dtype=np.float32)[:, None])
        t = DeviceTransport()
        mm = CollectiveMoveManager(g, transport=t)
        col.move_range_at_sync(LongRange(0, 2), 0, mm)   # self-destined
        mm.sync()
        assert mm.last_transport_stats.local == 1
        assert t.lifetime.local == 1 and t.lifetime.exchanges == 0

    def test_workload_transport_drives_the_steal_plane(self):
        # a workload-supplied transport instance is adopted by the
        # balancer, so steal_loop's ship_rows decision and the migration
        # windows always use one data plane
        from repro.core import (DistArrayWorkload, GLBConfig,
                                GlobalLoadBalancer)

        g = PlaceGroup(2)
        col = DistArray(g, track=True)
        col.add_chunk(0, LongRange(0, 8),
                      np.arange(8, dtype=np.float64)[:, None])
        for p in g.members:
            col.handle(p)
        t = DeviceTransport()
        glb = GlobalLoadBalancer(
            g, DistArrayWorkload(col, transport=t),
            GLBConfig(random_steal_attempts=0), device_loop=True)
        assert glb.transport is t
        glb.steal_loop(max_rounds=4)
        assert col.global_size() == 8

    def test_make_transport_specs(self):
        assert isinstance(make_transport(None), HostTransport)
        assert isinstance(make_transport("host"), HostTransport)
        assert isinstance(make_transport("device"), DeviceTransport)
        from repro.core import DistributedTransport
        assert isinstance(make_transport("distributed"),
                          DistributedTransport)
        t = DeviceTransport()
        assert make_transport(t) is t
        with pytest.raises(ValueError):
            make_transport("carrier-pigeon")
        with pytest.raises(TypeError):
            make_transport(DeviceTransport)   # class, not instance
        with pytest.raises(TypeError):
            make_transport(True)


# ---------------------------------------------------------------------------
# device data plane through the GLB steal loop (rows ride the all_to_all)
# ---------------------------------------------------------------------------
class TestMixedBucketHostCopies:
    """ISSUE 6 satellite: when one width class carries both pickled
    metadata and device-resident rows, only the host-decoded entries'
    row blocks may be copied to host — never the whole padded
    (n, S, W) receive buffer (which would drag the KV rows along)."""

    class _NpSpy:
        def __init__(self, real):
            self._real = real
            self.asarray_ndims = []

        def __getattr__(self, name):
            return getattr(self._real, name)

        def asarray(self, x, *a, **k):
            if hasattr(x, "ndim"):
                self.asarray_ndims.append(int(x.ndim))
            return self._real.asarray(x, *a, **k)

    def test_host_copies_are_per_block_not_full_buffer(self, monkeypatch):
        import jax

        import repro.core.transport as transport_mod

        g = PlaceGroup(2)
        m = DistIdMap(g)
        for p in g.members:
            m.handle(p)
        meta = "x" * 40                               # pickles to ~60 B
        m.put(0, 0, meta)
        m.put(0, 1, jax.device_put(np.arange(12, dtype=np.float32)))
        spy = self._NpSpy(np)
        monkeypatch.setattr(transport_mod, "np", spy)
        mm = CollectiveMoveManager(g, transport="device")
        m.move_at_sync(0, lambda k: 1, mm)
        mm.sync()
        st_ = mm.last_transport_stats
        assert st_.exchanges == 1      # one width class held both rows
        assert 3 not in spy.asarray_ndims
        assert m.get(1, 0) == meta
        assert np.array_equal(np.asarray(m.get(1, 1)),
                              np.arange(12, dtype=np.float32))


class TestDeviceStealTransport:
    def test_ship_rows_bitwise_matches_id_mode(self):
        from repro.core import (DistArrayWorkload, GLBConfig,
                                GlobalLoadBalancer)

        def run(transport):
            g = PlaceGroup(4)
            col = DistArray(g, track=True)
            col.add_chunk(0, LongRange(0, 64),
                          np.arange(192, dtype=np.float64).reshape(64, 3))
            for p in g.members:
                col.handle(p)
            glb = GlobalLoadBalancer(
                g, DistArrayWorkload(col),
                GLBConfig(random_steal_attempts=0, transport=transport),
                device_loop=True)
            res = glb.steal_loop(max_rounds=8)
            return col, res

        ch, rh = run("host")
        cd, rd = run("device")
        assert rh["stolen"] == rd["stolen"] and rh["rounds"] == rd["rounds"]
        for p in range(4):
            rowsh, idxh = ch.to_local_matrix(p)
            rowsd, idxd = cd.to_local_matrix(p)
            assert np.array_equal(idxh, idxd)
            assert np.array_equal(rowsh, rowsd)
            assert np.asarray(rowsh).dtype == np.asarray(rowsd).dtype
        assert ch.get_distribution().items() == cd.get_distribution().items()


# ---------------------------------------------------------------------------
# the elastic serving driver on the device transport (wiring smoke)
# ---------------------------------------------------------------------------
class TestServingOnDeviceTransport:
    def test_serving_sim_conserves_sequences(self):
        from repro.serving import ServingSim

        sim = ServingSim(n_replicas=4, arrival_rate=3.0, glb_period=3,
                         transport="device", seed=3)
        sim.run(12)
        d = sim.driver
        assert isinstance(d.transport, DeviceTransport)
        assert d.lost() == 0
        assert d.glb.stats.rebalances >= 1
        # the migration windows went through the device exchange
        assert d.transport.lifetime.exchanges >= 1

    def test_eviction_rehoming_rides_the_same_transport(self):
        # a replica death re-homes its sequences through the SAME data
        # plane as the regular migrations — the drain window must show
        # up in the device transport's wire counters
        from repro.serving import ServingSim

        sim = ServingSim(n_replicas=4, arrival_rate=4.0, glb_period=50,
                         fail_at={2: 1}, transport="device", seed=9)
        sim.run(6)
        d = sim.driver
        assert d.evicted == [1] and d.lost() == 0
        assert d.rehomed_seqs > 0
        assert d.transport.lifetime.exchanges >= 1, \
            "re-homing bypassed the device transport"

    def test_custom_transport_declares_its_plane(self):
        from repro.core import RelocationTransport

        class Custom:
            device_plane = True

            def exchange(self, group, counts, payloads):
                from repro.core import TransportStats
                return list(payloads), TransportStats(kind="custom")

        assert isinstance(Custom(), RelocationTransport)
        assert HostTransport.device_plane is False
        assert DeviceTransport.device_plane is True

    def test_driver_explicit_transport_beats_config(self):
        from repro.core import GLBConfig
        from repro.serving import ElasticServingDriver

        d = ElasticServingDriver(
            2, glb=GLBConfig(period=2, transport="host"),
            transport="device")
        assert isinstance(d.transport, DeviceTransport)
        assert d.workload.transport is d.transport
