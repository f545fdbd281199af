"""Fused relocation codec (ISSUE 10): the Pallas encode+pack and
unpack+decode kernels must deliver *bit-identical* collection state vs
the XLA composite path (and the host loopback) on every transport
scenario — chunk matrices across dtypes, aliased SeqKV pytrees, pickled
metadata, mixed width classes, fan-in overflow — selectable via
``kernels.ops.set_backend`` with zero API change.  Plus the satellites:
``pad_waste_bytes``/``codec_backend`` stats, the LRU-bounded jit
caches, and the property round-trip through the kernel pair."""
import contextlib

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core import (CollectiveMoveManager, DeviceTransport, DistArray,
                        DistIdMap, DistMap, HostTransport, LongRange,
                        PlaceGroup)
from repro.core import telemetry
from repro.kernels import ops, ref
from repro.kernels.reloc_codec import (LRUCache, from_words,
                                       jax_safe_dtype, kernel_cache_info,
                                       to_words, wire_rows)


@contextlib.contextmanager
def backend(name):
    prev = ops.get_backend()
    ops.set_backend(name)
    try:
        yield
    finally:
        ops.set_backend(prev)


@pytest.fixture
def fused():
    with backend("pallas_interpret"):
        yield


# ---------------------------------------------------------------------------
# kernel-level parity vs the XLA oracles
# ---------------------------------------------------------------------------
class TestKernelParity:
    @pytest.mark.parametrize("dtype", ["float32", "int32", "bfloat16",
                                       "uint8"])
    def test_encode_pack_matches_ref(self, dtype):
        if dtype == "bfloat16":
            ml_dtypes = pytest.importorskip("ml_dtypes")
            dt = np.dtype(ml_dtypes.bfloat16)
        else:
            dt = np.dtype(dtype)
        rng = np.random.default_rng(7)
        mat = (rng.integers(-100, 100, (7, 3)) / 4).astype(dt)
        nb = 3 * dt.itemsize
        W = 16
        # 2 places -> 4 pairs, 2 slots each; live slots permute rows
        idx = np.array([3, 6, 0, 0, 1, 0, 5, 2], np.int32)
        wid = np.array([nb, nb, 0, 0, nb, 0, nb, nb], np.int32)
        got = np.asarray(ops.reloc_encode_pack(
            mat, idx, wid, pairs=4, slots=2, width=W,
            impl="pallas_interpret"))
        want = np.asarray(ref.reloc_encode_pack_ref(
            mat, idx, wid, pairs=4, slots=2, width=W))
        assert np.array_equal(got, want)
        # live slots hold their row's words; 4-byte rows are the host
        # bytes, narrower ones the plane layout — both invert exactly
        rows = wire_rows(want, 2, W)
        assert np.array_equal(from_words(rows[0, 0], dt, 3), mat[3])
        assert np.array_equal(from_words(rows[3, 1], dt, 3), mat[2])
        if dt.itemsize == 4:
            u8 = np.frombuffer(mat.tobytes(), np.uint8).reshape(7, nb)
            assert np.array_equal(rows[0, 0].view(np.uint8)[:nb], u8[3])
        assert not rows[1].any()            # both of pair 1's slots empty
        assert not rows[0, 0, -(-nb // 4):].any()   # class padding zero

    def test_pack_rows_ragged_matches_ref(self):
        rng = np.random.default_rng(3)
        widths = [5, 12, 1, 8]
        rows = [rng.integers(0, 256, w).astype(np.uint8) for w in widths]
        # each byte row occupies whole words of the arena
        words = [np.pad(r, (0, (-w) % 4)).view(np.uint32)
                 for r, w in zip(rows, widths)]
        flat = np.concatenate(words)
        offs = np.zeros(8, np.int32)
        wids = np.zeros(8, np.int32)
        offs[:4] = np.cumsum([0] + [len(w) for w in words[:-1]])
        wids[:4] = widths
        got = np.asarray(ops.reloc_pack_rows(
            flat, offs, wids, pairs=4, slots=2, width=16,
            impl="pallas_interpret"))
        want = np.asarray(ref.reloc_pack_rows_ref(
            flat, offs, wids, pairs=4, slots=2, width=16))
        assert np.array_equal(got, want)
        got = wire_rows(got, 2, 16)
        assert np.array_equal(got[0, 0].view(np.uint8)[:5], rows[0])
        assert not got[0, 0].view(np.uint8)[8:].any()   # past the row
        assert not got[2:].any()   # empty pairs are zero capacity

    @pytest.mark.parametrize("dtype", ["float32", "int32", "int8"])
    def test_decode_rows_inverts_the_wire_format(self, dtype):
        dt = np.dtype(dtype)
        rng = np.random.default_rng(11)
        src = (rng.integers(-50, 50, (5, 4))).astype(dt)
        nb = 4 * dt.itemsize
        wire = to_words(src)
        padded = np.pad(wire, ((0, 0), (0, 8 - wire.shape[1])))
        for impl in ("pallas_interpret", "xla"):
            back = np.asarray(ops.reloc_decode_rows(
                padded, nbytes=nb, dtype=dt, impl=impl))
            assert back.dtype == dt and np.array_equal(back, src)

    def test_dispatch_env_seed_rejects_typos(self):
        with pytest.raises(ValueError):
            ops.set_backend("palas")   # typo must fail loudly
        assert ops.resolve_backend("pallas") == "pallas"
        # "auto" always resolves to a concrete backend (env may pin one)
        assert ops.resolve_backend() in ("xla", "pallas",
                                         "pallas_interpret", "xla_naive")


# ---------------------------------------------------------------------------
# the block gather of wide rows: small blocks, so rows stay small here
# ---------------------------------------------------------------------------
BLOCK, GROUP = 8, 2     # chunks a block moves; super-rows a rotate step
ROW_WORDS = {
    "one_block": 8 * 128,
    "block_and_chunk": 9 * 128,
    "every_remainder_bit": 15 * 128,         # 1 block + 4 + 2 + 1 chunks
    "blocks_bits_and_tail": 29 * 128 + 37,   # 3 blocks (past 2 buffers),
                                             # 4 + 1 chunks and a tail
    "block_ending_in_tail": 7 * 128 + 37,    # the tail in the last step
    "tail_only": 37,
}


@pytest.fixture
def small_blocks(monkeypatch):
    from repro.kernels import reloc_codec as rc

    monkeypatch.setattr(rc, "_BLOCK", BLOCK)
    monkeypatch.setattr(rc, "_GROUP", GROUP)
    monkeypatch.setattr(rc, "_CACHE", LRUCache(64))   # no stale programs


def _pack_both(words, offs, wids, *, pairs, slots, width):
    got = np.asarray(ops.reloc_pack_rows(
        words, offs, wids, pairs=pairs, slots=slots, width=width,
        impl="pallas_interpret"))
    want = np.asarray(ref.reloc_pack_rows_ref(
        words, offs, wids, pairs=pairs, slots=slots, width=width))
    return got, want


class TestBlockGather:
    @pytest.mark.parametrize("length", sorted(ROW_WORDS))
    @pytest.mark.parametrize("lane", [0, 2, 127])
    def test_pack_rows_matches_ref(self, small_blocks, lane, length):
        # pair 0: two rows back to back, so the first row's tail chunk
        # holds its neighbour's words, which must not leak onto the wire;
        # pair 1: an empty slot, then a row ending at the arena's end;
        # pairs 2 and 3 are empty
        n, W = ROW_WORDS[length], 16384             # 32 chunks a slot
        rng = np.random.default_rng(lane * 31 + n)
        words = rng.integers(1, 2**32, lane + 3 * n + 5,
                             dtype=np.uint64).astype(np.uint32)
        offs = np.array([lane, lane + n, 0, len(words) - n, 0, 0, 0, 0],
                        np.int32)
        wids = np.array([4 * n, 4 * n, 0, 4 * n - 3, 0, 0, 0, 0], np.int32)
        got, want = _pack_both(words, offs, wids, pairs=4, slots=2, width=W)
        assert np.array_equal(got, want)
        rows = wire_rows(got, 2, W)
        assert np.array_equal(rows[0, 0, :n], words[lane:lane + n])
        assert not rows[0, 0, n:].any()       # nothing past the row
        assert not rows[1, 0].any() and not rows[2:].any()

    @pytest.mark.parametrize("lane", [0, 2, 127])
    def test_chunk_class_boundary(self, small_blocks, lane):
        # the 512-byte class: one chunk a slot, whole or partial rows
        words = np.arange(1, 8 * 128, dtype=np.uint32)
        offs = np.array([lane, lane + 128, lane + 256, 0], np.int32)
        wids = np.array([512, 200, 512, 0], np.int32)
        got, want = _pack_both(words, offs, wids, pairs=2, slots=2,
                               width=512)
        assert np.array_equal(got, want)
        assert not got[1, 1].any()

    @pytest.mark.parametrize("short", [False, True])
    def test_encode_pack_relocation_shape(self, small_blocks, short):
        # 1000-byte float32 rows in the 1024-byte class: a row's two
        # chunks move as one block, masked past its 250 words, or past
        # fewer where a row is declared shorter than its words
        rng = np.random.default_rng(5)
        mat = rng.standard_normal((12, 250)).astype(np.float32)
        idx = rng.integers(0, 12, 4 * 8).astype(np.int32)
        wid = np.where(rng.random(4 * 8) < 0.7, 1000, 0).astype(np.int32)
        wid[:8] = 0                                    # an empty pair
        if short:
            wid[wid > 0] = 990
        got = np.asarray(ops.reloc_encode_pack(
            mat, idx, wid, pairs=4, slots=8, width=1024,
            impl="pallas_interpret"))
        want = np.asarray(ref.reloc_encode_pack_ref(
            mat, idx, wid, pairs=4, slots=8, width=1024))
        assert np.array_equal(got, want)
        assert not got[0].any()

    def test_codec_pack_span_counts_aligned_and_rotated_rows(self, fused):
        # two SeqKV rows (cache words + pos + token) of one pair: the
        # first starts the arena, the second two words past a chunk
        import jax
        from repro.serving.cache import SeqKV

        def run():
            g = PlaceGroup(2)
            m = DistIdMap(g)
            for p in g.members:
                m.handle(p)
            for k in range(2):
                cache = {"k": jax.device_put(np.full((16, 4), k, np.float32)),
                         "v": jax.device_put(np.full((16, 4), -k, np.float32)),
                         "pos": jax.device_put(np.full((1,), k, np.int32))}
                m.put(0, k, SeqKV(cache, jax.device_put(
                    np.full((1, 1), k + 7, np.int32))))
            mm = CollectiveMoveManager(g, transport="device")
            m.move_at_sync(0, lambda k: 1, mm)
            mm.sync()
            return [jax.tree_util.tree_map(
                lambda x: np.asarray(x).tobytes(), m.get(1, k).state)
                for k in range(2)]

        telemetry.enable()
        try:
            telemetry.tracer().clear()
            got = run()
            packs = [r for r in telemetry.tracer().records()
                     if r["name"] == "codec.pack"]
        finally:
            telemetry.reset()
            telemetry.disable()
        assert len(packs) == 1
        assert packs[0]["args"]["rows_aligned"] == 1
        assert packs[0]["args"]["rows_rotated"] == 1
        with backend("xla"):
            assert run() == got

    @pytest.mark.parametrize("width,counts", [
        (64, None),             # rows share a chunk: nothing to count
        (1024, (2, 1)),
        (8192, (2, 1)),
    ])
    def test_codec_pack_span_counts_host_tables(self, width, counts):
        words = np.arange(1, 2049, dtype=np.uint32)
        offs = np.array([0, 7, 256, 0], np.int32)
        wids = np.array([40, 40, 40, 0], np.int32)
        telemetry.enable()
        try:
            telemetry.tracer().clear()
            got = np.asarray(ops.reloc_pack_rows(
                words, offs, wids, pairs=2, slots=2, width=width,
                impl="pallas_interpret"))
            (pack,) = [r for r in telemetry.tracer().records()
                       if r["name"] == "codec.pack"]
        finally:
            telemetry.reset()
            telemetry.disable()
        assert np.array_equal(got, np.asarray(ref.reloc_pack_rows_ref(
            words, offs, wids, pairs=2, slots=2, width=width)))
        args = pack.get("args") or {}
        assert (args.get("rows_aligned"), args.get("rows_rotated")) == (
            counts or (None, None))


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 10), k=st.integers(1, 6), dt=st.integers(0, 2),
       extra=st.integers(0, 2))
def test_property_kernel_roundtrip(m, k, dt, extra):
    """encode_pack → slot slice → decode_rows is the identity on any
    chunk matrix, for any pow2 class padding."""
    dtype = [np.float32, np.int32, np.uint8][dt]
    rng = np.random.default_rng(m * 977 + k * 31 + dt)
    mat = (rng.integers(-999, 999, (m, k)) / 3).astype(dtype)
    nb = k * np.dtype(dtype).itemsize
    W = 1 << (max(nb, 8) - 1).bit_length() << extra
    slots = 1 << (m - 1).bit_length()
    idx = np.zeros(slots, np.int32)
    wid = np.zeros(slots, np.int32)
    idx[:m] = np.arange(m)
    wid[:m] = nb
    buf = ops.reloc_encode_pack(mat, idx, wid, pairs=1, slots=slots,
                                width=W, impl="pallas_interpret")
    back = np.asarray(ops.reloc_decode_rows(
        wire_rows(buf, slots, W)[0, :m], nbytes=nb, dtype=np.dtype(dtype),
        impl="pallas_interpret"))
    assert back.dtype == mat.dtype
    assert np.array_equal(back.view(np.uint8), mat.view(np.uint8))


# ---------------------------------------------------------------------------
# window-level parity: fused backend vs XLA composite vs host loopback
# ---------------------------------------------------------------------------
class TestFusedWindowParity:
    def test_full_window_chain_bitwise_parity(self):
        # the ISSUE 5 multi-window scenario (ranges, keyed SeqKV moves,
        # eviction drain, admission-time puts) — the fused codec must
        # reproduce the composite path's delivered state bit for bit
        from test_transport import _drive_windows

        with backend("pallas_interpret"):
            fused = _drive_windows(DeviceTransport(), 2)
        with backend("xla"):
            composite = _drive_windows(DeviceTransport(), 2)
        host = _drive_windows(HostTransport(), 2)
        assert fused == composite == host

    def test_bounded_exchange_rounds_parity(self, monkeypatch):
        # a send buffer over the device-memory bound runs as several
        # exchange rounds; entries straddling rounds reassemble exactly
        from test_transport import _drive_windows
        from repro.core import transport

        monkeypatch.setattr(transport, "_EXCHANGE_BYTES", 256)
        t = DeviceTransport()
        with backend("pallas_interpret"):
            split = _drive_windows(t, 2)
        assert split == _drive_windows(HostTransport(), 2)
        assert t.lifetime.exchanges > 4

    @pytest.mark.parametrize("dtype", [np.float32, np.int32, np.float64])
    def test_chunk_moves_parity_across_dtypes(self, dtype, fused):
        # float64 is NOT jax-safe under x64-off: it must transparently
        # take the byte-arena path inside the same fused window
        def run():
            g = PlaceGroup(3)
            col = DistArray(g, track=True)
            col.add_chunk(0, LongRange(0, 9),
                          np.arange(27).reshape(9, 3).astype(dtype))
            for p in g.members:
                col.handle(p)
            mm = CollectiveMoveManager(g, transport="device")
            col.move_range_at_sync(LongRange(0, 4), 1, mm)
            col.move_at_sync_count(0, 2, 2, mm)
            mm.sync()
            return [(col.ranges(p),
                     np.asarray(col.to_local_matrix(p)[0]).tobytes(),
                     np.asarray(col.to_local_matrix(p)[0]).dtype)
                    for p in g.members], mm.last_transport_stats

        got, st_f = run()
        with backend("xla"):
            want, st_x = run()
        assert got == want
        assert st_f.codec_backend == "pallas_interpret"
        assert st_x.codec_backend == "xla"
        # identical wire accounting on both paths
        for f in ("rows", "row_bytes", "wire_bytes", "pad_waste_bytes",
                  "width", "exchanges"):
            assert getattr(st_f, f) == getattr(st_x, f), f

    def test_mixed_width_classes_and_aliased_seqkv(self, fused):
        import jax
        from repro.serving.cache import SeqKV

        def run():
            g = PlaceGroup(2)
            small = DistIdMap(g)
            big = DistIdMap(g)
            for p in g.members:
                small.handle(p)
                big.handle(p)
            for k in range(3):
                small.put(0, k, np.full(2, k, np.float32))
                page = jax.device_put(np.full((8, 4), k, np.float32))
                big.put(0, k, SeqKV({"k": page, "v": page},
                                    jax.device_put(
                                        np.full((1, 1), k, np.int32))))
            mm = CollectiveMoveManager(g, transport="device")
            small.move_at_sync(0, lambda k: 1, mm)
            big.move_at_sync(0, lambda k: 1, mm)
            mm.sync()
            snap = []
            for k in range(3):
                kv = big.get(1, k)
                assert kv.state["k"] is kv.state["v"]   # alias rebound
                snap.append((np.asarray(small.get(1, k)).tobytes(),
                             np.asarray(kv.state["k"]).tobytes(),
                             np.asarray(kv.token).tobytes()))
            return snap, mm.last_transport_stats.exchanges

        got, exchanges = run()
        assert exchanges == 2      # one fused kernel per width class
        with backend("xla"):
            want, _ = run()
        assert got == want

    def test_fan_in_overflow_parity(self, fused):
        # 3 senders converge on place 0 — per-pair slotting makes
        # overflow structurally impossible; state must match the
        # composite path, which sizes capacity by both sides
        def run():
            g = PlaceGroup(4)
            m = DistMap(g)
            for p in g.members:
                m.handle(p)
            for src in (1, 2, 3):
                for j in range(8):
                    m.put(src, f"{src}-{j}",
                          np.full(4, src * 10 + j, np.float32))
            mm = CollectiveMoveManager(g, transport="device")
            for src in (1, 2, 3):
                m.move_at_sync(src, lambda k: 0, mm)
            mm.sync()
            return sorted((k, np.asarray(m.get(0, k)).tobytes())
                          for k in m.keys(0))

        got = run()
        with backend("xla"):
            want = run()
        assert got == want and len(got) == 24

    def test_pickled_metadata_rides_the_fused_arena(self, fused):
        # non-array values (pickle path) share the window with device
        # pytrees: the mixed bucket goes through the pack_rows arena
        import jax

        def run():
            g = PlaceGroup(2)
            m = DistIdMap(g)
            for p in g.members:
                m.handle(p)
            m.put(0, 0, "metadata-" * 5)
            m.put(0, 1, jax.device_put(np.arange(12, dtype=np.float32)))
            mm = CollectiveMoveManager(g, transport="device")
            m.move_at_sync(0, lambda k: 1, mm)
            mm.sync()
            return (m.get(1, 0), np.asarray(m.get(1, 1)).tobytes())

        got = run()
        with backend("xla"):
            want = run()
        assert got == want

    def test_device_steal_ship_rows_parity(self, fused):
        from repro.core import (DistArrayWorkload, GLBConfig,
                                GlobalLoadBalancer)

        def run(transport):
            g = PlaceGroup(4)
            col = DistArray(g, track=True)
            col.add_chunk(0, LongRange(0, 32),
                          np.arange(64, dtype=np.float32).reshape(32, 2))
            for p in g.members:
                col.handle(p)
            glb = GlobalLoadBalancer(
                g, DistArrayWorkload(col),
                GLBConfig(random_steal_attempts=0, transport=transport),
                device_loop=True)
            res = glb.steal_loop(max_rounds=6)
            return col, res

        ch, rh = run("host")
        cd, rd = run("device")   # rows decode through the fused kernel
        assert rh["stolen"] == rd["stolen"]
        for p in range(4):
            rowsh, idxh = ch.to_local_matrix(p)
            rowsd, idxd = cd.to_local_matrix(p)
            assert np.array_equal(idxh, idxd)
            assert np.array_equal(np.asarray(rowsh), np.asarray(rowsd))


# ---------------------------------------------------------------------------
# satellites: stats fields, LRU caches, metrics publishing
# ---------------------------------------------------------------------------
class TestCodecStats:
    def test_pad_waste_and_backend_in_stats(self):
        def run():
            g = PlaceGroup(2)
            col = DistArray(g, track=False)
            # 3-byte rows pad to the 8-byte class floor: 5 B/row waste
            col.add_chunk(0, LongRange(0, 6),
                          np.arange(18, dtype=np.int8).reshape(6, 3))
            col.handle(1)
            mm = CollectiveMoveManager(g, transport="device")
            col.move_range_at_sync(LongRange(0, 4), 1, mm)
            mm.sync()
            return mm.last_transport_stats

        st_ = run()
        assert st_.row_bytes == 4 * 3
        assert st_.wire_bytes == 4 * 8
        assert st_.pad_waste_bytes == 4 * 5
        assert st_.codec_backend == ops.resolve_backend()
        with backend("pallas_interpret"):
            st_f = run()
        assert st_f.pad_waste_bytes == st_.pad_waste_bytes
        assert st_f.codec_backend == "pallas_interpret"
        d = st_f.as_dict("t.")
        assert d["t.pad_waste_bytes"] == 20
        assert d["t.codec_backend"] == "pallas_interpret"

    def test_lifetime_publish_includes_pad_waste(self):
        telemetry.enable()
        try:
            g = PlaceGroup(2)
            col = DistArray(g, track=False)
            col.add_chunk(0, LongRange(0, 4),
                          np.arange(4, dtype=np.int8)[:, None])
            col.handle(1)
            t = DeviceTransport()
            mm = CollectiveMoveManager(g, transport=t)
            col.move_range_at_sync(LongRange(0, 2), 1, mm)
            mm.sync()
            d = telemetry.metrics_dict()
            assert d["transport.device.pad_waste_bytes"] \
                == t.lifetime.pad_waste_bytes > 0
            # the jit-cache publisher rides the same registry
            assert d["transport.device.jit_cache_size"] >= 1
            assert "transport.device.jit_cache_evictions" in d
        finally:
            telemetry.reset()
            telemetry.disable()


class TestLRUCaches:
    def test_lru_cache_counters_and_eviction(self):
        c = LRUCache(2)
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1 and c.hits == 1
        c.put("c", 3)              # evicts "b" (LRU after the get)
        assert c.get("b") is None and c.misses == 1
        assert c.evictions == 1 and len(c) == 2
        assert c.info()["evictions"] == 1

    def test_transport_jit_cache_bounded(self):
        t = DeviceTransport(jit_cache_cap=1)
        t._exchange_fn(2, 8, 8)
        t._exchange_fn(2, 8, 16)   # different width class: evicts
        assert len(t._fns) == 1 and t._fns.evictions == 1
        t._exchange_fn(2, 8, 16)   # still cached
        assert t._fns.hits == 1

    def test_kernel_cache_is_lru(self):
        info = kernel_cache_info()
        assert info["cap"] >= 1
        ops.reloc_decode_rows(np.zeros((2, 2), np.uint32), nbytes=4,
                              dtype=np.float32, impl="pallas_interpret")
        ops.reloc_decode_rows(np.zeros((2, 2), np.uint32), nbytes=4,
                              dtype=np.float32, impl="pallas_interpret")
        info2 = kernel_cache_info()
        assert info2["hits"] > info["hits"]

    def test_loop_cache_is_bounded(self):
        from repro.core import spmd_glb

        assert isinstance(spmd_glb._LOOP_CACHE, LRUCache)


class TestDtypeGate:
    def test_jax_safe_dtype(self):
        assert jax_safe_dtype(np.float32)
        assert jax_safe_dtype(np.int8)
        assert jax_safe_dtype(np.uint8)
        assert not jax_safe_dtype(object)
        assert not jax_safe_dtype(np.bool_)   # kind 'b': byte path
        import jax

        if not jax.config.jax_enable_x64:
            assert not jax_safe_dtype(np.float64)
            assert not jax_safe_dtype(np.int64)

    def test_encode_rows_raw_gates_unsafe_dtypes(self):
        col = DistArray(PlaceGroup(2), track=False)
        ok = col.encode_rows_raw(
            (LongRange(0, 3), np.zeros((3, 2), np.float32)))
        assert ok is not None and ok[0].shape == (3, 2)
        assert col.encode_rows_raw(
            (LongRange(0, 3), np.zeros((3, 2), np.float64))) is None
        assert col.encode_rows_raw(
            (LongRange(0, 0), np.zeros((0, 2), np.float32))) is None

    def test_encode_rows_donate_is_a_view(self):
        col = DistArray(PlaceGroup(2), track=False)
        rows = np.arange(8, dtype=np.float32).reshape(4, 2)
        u8, _ = col.encode_rows((LongRange(0, 4), rows), donate=True)
        assert u8.base is not None          # zero-copy view
        copy, _ = col.encode_rows((LongRange(0, 4), rows))
        assert np.array_equal(u8, copy)     # same wire bytes
