"""Pallas relocation-codec kernels: chunks → all_to_all buffer → chunks.

The device transport packs every payload row into one send buffer with
a slot block per ``(src, dest)`` pair, and unpacks the delivered blocks
on the other side.  On the device every wire row is a sequence of
**32-bit words**: a TPU lays narrow arrays out in packed tiles, so any
byte view (an array whose minor dimension is an element's byte count)
costs 32-64x its size in padding, and Mosaic cannot change bit widths
inside a kernel.  Host rows stay bytes; their little-endian word view
is the same data.

Typed arrays become words with :func:`to_words` — 4-byte dtypes are a
bitcast, narrower ones pack contiguous *planes* of the last axis into
each word (word ``i`` of a bf16 row holds elements ``i`` and
``i + k/2``), 8-byte ones put low words before high words — and come
back with :func:`from_words`.  Both use only slices, shifts and
concatenation, which the TPU runs without padding.

The send buffer is ``(pairs, chunks, 1, 128)`` uint32: each pair's
slots back to back as one stream of 512-byte chunks (slot ``r`` is
words ``[r*W/4, (r+1)*W/4)`` of its pair's stream).  Two kernels do the
device work, dispatched through :mod:`repro.kernels.ops`
(``reloc_encode_pack``/``reloc_pack_rows``/``reloc_decode_rows``) —
never call ``pl.pallas_call`` directly outside ``kernels/`` (repro-lint
RL009):

* :func:`pack_rows` — the slot gather.  Rows sit back to back in a word
  arena at any word offset; each live slot receives its row's words,
  zeroed past the row's width.  The arena stays in HBM as ``(R, 1,
  128)`` super-rows — the finest granularity the TPU's DMA engine
  addresses.  A wide row moves in blocks of up to :data:`_BLOCK`
  chunks, the remainder in power-of-two pieces (DMA sizes are static):
  each block is fetched with one more super-row into VMEM, realigned
  with a dynamic lane rotate and sent on in one copy, the next block's
  fetch in flight meanwhile.  The words past the row's last live one
  (the next row's) are masked off in the rotate, so they stay off the
  wire.  Rows narrower than a chunk share one, each fetched as two
  super-rows.  Empty slots are never written: the output aliases a
  zero buffer.
* :func:`encode_pack` — the same gather for a typed chunk matrix: its
  word rows (padded to the row class) are the arena.
* :func:`decode_rows` — received word rows → the manifest's dtype: trim
  the class padding and reinterpret, in row tiles.

Both packing entry points open a ``codec.pack`` telemetry span that
counts the live wide rows starting on a chunk boundary
(``rows_aligned``) and the others (``rows_rotated``), where the slot
tables are host arrays.

All kernels run under ``interpret=True`` on CPU (the parity target,
bit-identical to :mod:`repro.kernels.ref`); the compiled path is the
TPU execution target.

The jitted entry points are named for what they run, so a profile
names its programs ``jit_reloc_encode_pack``, ``jit_reloc_pack`` and
``jit_reloc_decode`` (the kernels inside keep their ``name``).  Jitted
kernel instances are cached per static shape in a bounded
:class:`LRUCache` so long elastic runs (where the place count changes
on every resize) cannot grow the cache without bound.
"""
from __future__ import annotations

import functools
import os
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import tpu_compiler_params

__all__ = ["LRUCache", "encode_pack", "pack_rows", "decode_rows",
           "kernel_cache_info", "jax_safe_dtype", "to_words", "from_words",
           "n_words", "wire_rows"]


class LRUCache:
    """Tiny bounded mapping for jitted-callable caches.

    ``get`` refreshes recency, ``put`` evicts the least-recently-used
    entry past ``cap`` and counts evictions — the counter is the signal
    a long elastic run is thrashing its specializations (every resize
    changes ``n``) rather than silently leaking compiled programs.
    """

    def __init__(self, cap: int):
        self.cap = max(int(cap), 1)
        self._d: OrderedDict = OrderedDict()
        self.evictions = 0
        self.hits = 0
        self.misses = 0

    def get(self, key):
        try:
            val = self._d[key]
        except KeyError:
            self.misses += 1
            return None
        self._d.move_to_end(key)
        self.hits += 1
        return val

    def put(self, key, val) -> None:
        self._d[key] = val
        self._d.move_to_end(key)
        while len(self._d) > self.cap:
            self._d.popitem(last=False)
            self.evictions += 1

    def __len__(self) -> int:
        return len(self._d)

    def info(self) -> dict:
        return {"size": len(self._d), "cap": self.cap,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}


_CACHE = LRUCache(int(os.environ.get("REPRO_KERNEL_CACHE_CAP", "64")))


def kernel_cache_info() -> dict:
    """Size/hit/eviction counters of the module's jit-instance cache."""
    return _CACHE.info()


def jax_safe_dtype(dt) -> bool:
    """Can ``dt`` ride a ``jnp.asarray`` round trip bit-exactly under
    the default (x64-off) config?  float64/int64 silently downcast, and
    object dtypes are pointers — both must take the byte-view path."""
    dt = np.dtype(dt)
    if dt.hasobject or dt.kind not in "fiu":
        return False
    if dt.itemsize > 4:
        import jax

        return bool(jax.config.jax_enable_x64)
    return True


# ---------------------------------------------------------------------------
# typed arrays <-> 32-bit words (slices and shifts only: no padded views)
# ---------------------------------------------------------------------------
_UINT = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}
_CHUNK = 512            # bytes per output chunk: one (1, 128) uint32 tile
_LANES = _CHUNK // 4


def _xp(x):
    return np if isinstance(x, (np.ndarray, np.generic)) else jnp


def _bitcast(x, dtype):
    """Same-width reinterpretation (free on every backend)."""
    if _xp(x) is np:
        return np.ascontiguousarray(x).view(dtype)
    return jax.lax.bitcast_convert_type(x, dtype)


def n_words(k: int, itemsize: int) -> int:
    """Words :func:`to_words` makes of ``k`` elements of ``itemsize``."""
    return -(-k * itemsize // 4)


def to_words(x):
    """``(..., k)`` array (numpy or jax, any fixed-width dtype) →
    ``(..., n_words(k, itemsize))`` uint32 in the codec's word layout."""
    xp = _xp(x)
    dt = np.dtype(x.dtype)
    if dt == np.bool_:
        x, dt = x.astype(xp.uint8), np.dtype(np.uint8)
    s = dt.itemsize
    u = _bitcast(x, _UINT[s])
    if s == 4:
        return u
    if s == 8:
        return xp.concatenate([(u & 0xFFFFFFFF).astype(xp.uint32),
                               (u >> 32).astype(xp.uint32)], axis=-1)
    q, k = 4 // s, u.shape[-1]
    kw = -(-k // q)
    if kw * q != k:
        u = xp.pad(u, [(0, 0)] * (u.ndim - 1) + [(0, kw * q - k)])
    out = u[..., :kw].astype(xp.uint32)
    for j in range(1, q):
        out = out | (u[..., j * kw:(j + 1) * kw].astype(xp.uint32)
                     << (8 * s * j))
    return out


def from_words(w, dtype, k: int):
    """Inverse of :func:`to_words`: ``(..., >= n_words)`` uint32 →
    ``(..., k)`` of ``dtype`` (extra trailing words are ignored)."""
    xp = _xp(w)
    dt = np.dtype(dtype)
    s = 1 if dt == np.bool_ else dt.itemsize
    if s == 4:
        return _bitcast(w[..., :k], dt)
    if s == 8:
        u = w[..., :k].astype(xp.uint64) \
            | (w[..., k:2 * k].astype(xp.uint64) << 32)
        return _bitcast(u, dt)
    q, kw = 4 // s, -(-k // (4 // s))
    w = w[..., :kw]
    mask = (1 << (8 * s)) - 1
    planes = [((w >> (8 * s * j)) & mask).astype(_UINT[s])
              for j in range(q)]
    u = xp.concatenate(planes, axis=-1)[..., :k]
    if dt == np.bool_:
        return u.astype(xp.bool_)
    return _bitcast(u, dt)


def wire_rows(buf, slots: int, width: int):
    """``(pairs, chunks, 1, 128)`` send buffer → ``(pairs, slots,
    width // 4)`` word rows (a view for tests and host readers)."""
    pairs, wq = buf.shape[0], width // 4
    return buf.reshape(pairs, -1)[:, :slots * wq].reshape(pairs, slots, wq)


def _chunks(slots: int, width: int) -> int:
    return max(slots * width // _CHUNK, 1)


def _arena(words, wq: int):
    """1-D word arena → ``(R, 1, 128)`` super-rows, zero-padded so a
    fetch of ``wq`` words anywhere inside reads in bounds (two
    super-rows past its start)."""
    n = words.shape[0] + wq + 2 * _LANES
    n += (-n) % _LANES
    return jnp.pad(words, (0, n - words.shape[0])).reshape(-1, 1, _LANES)


def _arena_rows(n_words_: int, wq: int) -> int:
    return -(-(n_words_ + wq + 2 * _LANES) // _LANES)


# ---------------------------------------------------------------------------
# the slot gather: word arena -> (pairs, chunks, 1, 128) send buffer
# ---------------------------------------------------------------------------
_BLOCK = 256        # chunks one block moves (a power of two)
_GROUP = 8          # super-rows rotated per vector step (a power of two)


def _rotate_into(src, dst, n: int, c, keep):
    """``dst[j]`` = words ``[c, c + 128)`` of super-rows ``src[j],
    src[j + 1]``, for ``j < n``: the chunks of a row that starts at lane
    ``c`` (``src`` holds ``n + 1`` super-rows).  Words from ``keep`` on
    (counted from ``dst[0]``'s first) are zeroed: past the row's end
    they are the next row's."""
    g = min(_GROUP, n)
    lane = jax.lax.broadcasted_iota(jnp.int32, (g, 1, _LANES), 2)
    word = jax.lax.broadcasted_iota(jnp.int32, (g, 1, _LANES), 0) \
        * _LANES + lane
    shift = (_LANES - c) % _LANES

    def step(i, carry):
        a = pltpu.roll(src[pl.ds(i * g, g)], shift, 2)
        b = pltpu.roll(src[pl.ds(i * g + 1, g)], shift, 2)
        y = jnp.where(lane < _LANES - c, a, b)
        dst[pl.ds(i * g, g)] = jnp.where(word < keep - i * g * _LANES, y,
                                         jnp.uint32(0))
        return carry

    jax.lax.fori_loop(0, n // g, step, 0)


def _block(wq: int) -> int:
    """Chunks a block moves in the ``wq``-word class: no more than a
    slot holds."""
    return min(_BLOCK, wq // _LANES)


def _pieces(rem, at0, block: int):
    """Split ``rem`` (< ``block``) chunks from chunk ``at0`` into static
    power-of-two sizes, largest first: ``(size, start, taken)``."""
    b = block // 2
    while b:
        yield b, at0 + (rem & ~(2 * b - 1)), (rem & b) != 0
        b //= 2


def _gather_kernel(off_ref, len_ref, arena, _zeros, out, *scratch,
                   slots: int, wq: int):
    """One grid step packs one (src, dest) pair's ``slots`` rows.

    ``off_ref``/``len_ref`` (SMEM, ``(1, 1, slots)``): word offset and
    live word count of each slot's row in ``arena`` (HBM super-rows).
    ``out`` (HBM) aliases a zero buffer, so only chunks holding live
    words are written."""
    if wq < _LANES:
        _narrow(off_ref, len_ref, arena, out, *scratch, slots=slots, wq=wq)
        return
    p = pl.program_id(0)
    blk, rot, sem = scratch
    nsub = wq // _LANES
    B = _block(wq)

    def row(off, n, r):
        # every read stays inside the arena, whatever the offset
        s0 = jnp.minimum(off // _LANES, arena.shape[0] - nsub - 2)
        c = off % _LANES
        nc = (n + _LANES - 1) // _LANES     # chunks holding live words
        nb = nc // B

        def fetch(at, s, size=B):
            return pltpu.make_async_copy(
                arena.at[pl.ds(s0 + at, size + 1)],
                blk.at[s, pl.ds(0, size + 1)], sem.at[0, s])

        def emit(at, s, size=B):
            return pltpu.make_async_copy(
                rot.at[s, pl.ds(0, size)],
                out.at[p, pl.ds(r * nsub + at, size)], sem.at[1, s])

        def rotate(at, s, size=B):
            _rotate_into(blk.at[s], rot.at[s], size, c, n - at * _LANES)

        @pl.when(nb > 0)
        def _():
            fetch(0, 0).start()

        def block(i, carry):
            # block i + 1 is fetched while block i is rotated and sent
            s = i % 2

            @pl.when(i + 1 < nb)
            def _():
                fetch((i + 1) * B, 1 - s).start()

            fetch(i * B, s).wait()

            @pl.when(i >= 2)
            def _():
                emit((i - 2) * B, s).wait()

            rotate(i * B, s)
            emit(i * B, s).start()
            return carry

        def drain(i, carry):
            emit(i * B, i % 2).wait()
            return carry

        jax.lax.fori_loop(0, nb, block, 0)
        jax.lax.fori_loop(jnp.maximum(nb - 2, 0), nb, drain, 0)
        for b, at, taken in _pieces(nc % B, nb * B, B):
            @pl.when(taken)
            def _():
                f = fetch(at, 0, b)
                f.start()
                f.wait()
                rotate(at, 0, b)
                e = emit(at, 0, b)
                e.start()
                e.wait()

    def slot(r, carry):
        n = jnp.minimum(len_ref[0, 0, r], wq)
        pl.when(n > 0)(lambda: row(off_ref[0, 0, r], n, r))
        return carry

    jax.lax.fori_loop(0, slots, slot, 0)


def _narrow(off_ref, len_ref, arena, out, scr, ob, sem, *, slots: int,
            wq: int):
    """Rows narrower than a chunk: ``128 // wq`` of them share one, each
    fetched as two super-rows."""
    p = pl.program_id(0)
    rows = arena.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    per = min(_LANES // wq, slots)

    def fetch(q, keep):
        # arena words [q, q + 128): two super-rows, rotated so word q
        # lands in lane 0; words at or past ``keep`` zeroed
        s0 = jnp.minimum(q // _LANES, rows - 2)
        c = q % _LANES
        cp = pltpu.make_async_copy(arena.at[pl.ds(s0, 2)], scr, sem)
        cp.start()
        cp.wait()
        shift = (_LANES - c) % _LANES
        y = jnp.where(lane < _LANES - c, pltpu.roll(scr[0], shift, 1),
                      pltpu.roll(scr[1], shift, 1))
        return jnp.where(lane < keep, y, jnp.uint32(0))

    def group(g, carry):
        def one(t, acc):
            r = g * per + t
            n = jnp.minimum(len_ref[0, 0, r], wq)
            y = jax.lax.cond(
                n > 0, lambda: fetch(off_ref[0, 0, r], n),
                lambda: jnp.zeros((1, _LANES), jnp.uint32))
            return acc | pltpu.roll(y, t * wq, 1)

        ob[0] = jax.lax.fori_loop(0, per, one,
                                  jnp.zeros((1, _LANES), jnp.uint32))
        cp = pltpu.make_async_copy(ob, out.at[p, pl.ds(g, 1)], sem)
        cp.start()
        cp.wait()
        return carry

    jax.lax.fori_loop(0, slots // per, group, 0)


def _gather_call(pairs: int, slots: int, width: int, rows: int,
                 interpret: bool):
    """Jitted ``(arena super-rows, word offsets, word counts) → send
    buffer``, cached per static shape."""
    key = ("gather", pairs, slots, width, rows, interpret)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    chunks = _chunks(slots, width)
    wq = width // 4
    tab = pl.BlockSpec((1, 1, slots), lambda p: (p, 0, 0),
                       memory_space=pltpu.SMEM)
    if wq < _LANES:
        scratch = [pltpu.VMEM((2, 1, _LANES), jnp.uint32),
                   pltpu.VMEM((1, 1, _LANES), jnp.uint32),
                   pltpu.SemaphoreType.DMA(())]
    else:
        # two blocks in turn; a (1, 128) super-row fills an (8, 128)
        # tile of VMEM: 4 KiB
        block = _block(wq)
        scratch = [pltpu.VMEM((2, block + 1, 1, _LANES), jnp.uint32),
                   pltpu.VMEM((2, block, 1, _LANES), jnp.uint32),
                   pltpu.SemaphoreType.DMA((2, 2))]
    call = pl.pallas_call(
        functools.partial(_gather_kernel, slots=slots, wq=wq),
        grid=(pairs,),
        in_specs=[tab, tab, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((pairs, chunks, 1, _LANES),
                                       jnp.uint32),
        scratch_shapes=scratch,
        input_output_aliases={3: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="reloc_pack_rows",
    )

    def reloc_gather(arena, offsets, n_live):
        zeros = jnp.zeros((pairs, chunks, 1, _LANES), jnp.uint32)
        return call(offsets.reshape(pairs, 1, slots),
                    n_live.reshape(pairs, 1, slots), arena, zeros)

    fn = jax.jit(reloc_gather)
    _CACHE.put(key, fn)
    return fn


def _check_width(width: int) -> None:
    if width < 8 or width & (width - 1):
        raise ValueError(f"row width class must be a power of two >= 8, "
                         f"got {width}")


def _live_words(widths, wq: int):
    return jnp.minimum((widths + 3) // 4, wq)


def pack_rows(words, offsets, widths, *, pairs: int, slots: int,
              width: int, interpret: bool = False):
    """Pre-encoded word rows → send buffer.

    ``words``: 1-D uint32 arena holding every row back to back;
    ``offsets``: (pairs*slots,) int32 word offset of each slot's row;
    ``widths``: (pairs*slots,) int32 byte width of each slot's row (0 →
    empty slot).  Returns the ``(pairs, chunks, 1, 128)`` uint32 send
    buffer (see :func:`wire_rows`).
    """
    _check_width(width)
    words = jnp.asarray(words, jnp.uint32)
    fn = _pack_rows_call(pairs, slots, width, int(words.shape[0]),
                         interpret)
    from ..core import telemetry   # core imports this module

    with telemetry.span("codec.pack") as sp:
        if sp:
            _count_rows(sp, offsets, widths, width)
        return fn(words, jnp.asarray(offsets, jnp.int32),
                  jnp.asarray(widths, jnp.int32))


def _count_rows(sp, offsets, widths, width: int) -> None:
    """Stamp ``rows_aligned`` / ``rows_rotated`` on a ``codec.pack``
    span: the live wide-class slots whose word offset is, or is not, a
    multiple of 128 (a row that starts on a chunk boundary rotates by
    no lanes).  Tables that live on the device are not counted: reading
    them would wait for the device; nor are rows narrower than a chunk,
    which share one."""
    if (width // 4 >= _LANES and isinstance(offsets, np.ndarray)
            and isinstance(widths, np.ndarray)):
        live = widths > 0
        aligned = int(np.count_nonzero(offsets[live] % _LANES == 0))
        sp.set(rows_aligned=aligned,
               rows_rotated=int(np.count_nonzero(live)) - aligned)


def _pack_rows_call(pairs: int, slots: int, width: int, n: int,
                    interpret: bool):
    key = ("pack", pairs, slots, width, n, interpret)
    fn = _CACHE.get(key)
    if fn is None:
        wq = width // 4
        gather = _gather_call(pairs, slots, width, _arena_rows(n, wq),
                              interpret)

        def reloc_pack(words, offsets, widths):
            return gather(_arena(words, wq), offsets,
                          _live_words(widths, wq))

        fn = jax.jit(reloc_pack)
        _CACHE.put(key, fn)
    return fn


def _encode_pack_call(pairs: int, slots: int, width: int, m: int, k: int,
                      dtype, interpret: bool):
    key = ("enc", pairs, slots, width, m, k, str(dtype), interpret)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    wq = width // 4
    kw = n_words(k, np.dtype(dtype).itemsize)
    if kw > wq:
        raise ValueError(f"{k} x {np.dtype(dtype)} rows exceed the "
                         f"{width}-byte class")
    gather = _gather_call(pairs, slots, width, _arena_rows(m * wq, wq),
                          interpret)

    def reloc_encode_pack(mat, idx, widths):
        words = jnp.pad(to_words(mat), ((0, 0), (0, wq - kw)))
        offsets = jnp.clip(idx, 0, m - 1) * wq
        return gather(_arena(words.reshape(-1), wq), offsets,
                      _live_words(widths, wq))

    fn = jax.jit(reloc_encode_pack)
    _CACHE.put(key, fn)
    return fn


def encode_pack(mat, idx, widths, *, pairs: int, slots: int, width: int,
                interpret: bool = False):
    """Rows of ``mat`` (any jax-safe dtype) → send buffer.

    ``mat``: (m, k) chunk rows; ``idx``: (pairs*slots,) int32 source-row
    index per buffer slot (clamped; ignored where ``widths`` is 0);
    ``widths``: (pairs*slots,) int32 — ``k*itemsize`` for live slots, 0
    for empty capacity slots (zero-filled).  Returns the ``(pairs,
    chunks, 1, 128)`` uint32 send buffer with the destination
    permutation, class padding and capacity zeroing applied.
    """
    _check_width(width)
    mat = jnp.asarray(mat)
    m, k = int(mat.shape[0]), int(mat.shape[1])
    fn = _encode_pack_call(pairs, slots, width, m, k, mat.dtype,
                           interpret)
    from ..core import telemetry

    with telemetry.span("codec.pack") as sp:
        if sp and isinstance(idx, np.ndarray):
            _count_rows(sp, np.clip(idx, 0, m - 1) * (width // 4), widths,
                        width)
        return fn(mat, jnp.asarray(idx, jnp.int32),
                  jnp.asarray(widths, jnp.int32))


# ---------------------------------------------------------------------------
# unpack+decode: received word rows -> chunk matrix
# ---------------------------------------------------------------------------
_DECODE_TILE_BYTES = 1 << 20


def _decode_kernel(x_ref, o_ref):
    cols = o_ref.shape[1]
    o_ref[...] = jax.lax.bitcast_convert_type(x_ref[:, :cols],
                                              o_ref.dtype)


def _decode_call(m: int, wq: int, nbytes: int, dtype, interpret: bool):
    key = ("dec", m, wq, nbytes, str(np.dtype(dtype)), interpret)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    dt = np.dtype(dtype)
    k = nbytes // dt.itemsize
    kw = n_words(k, dt.itemsize)
    word_dt = jnp.dtype(dt) if dt.itemsize == 4 else jnp.uint32
    # row tiles of ~1 MiB; rows wider than that tile the lanes too
    lanes = min(wq, _DECODE_TILE_BYTES // 4)
    tm = max(_DECODE_TILE_BYTES // (4 * lanes) // 8 * 8, 8)
    tm = m if m <= tm else tm
    if lanes == wq:
        in_block, out_block = (tm, wq), (tm, kw)
        grid = (pl.cdiv(m, tm), 1)
    else:
        in_block = out_block = (tm, lanes)
        grid = (pl.cdiv(m, tm), pl.cdiv(kw, lanes))
    call = pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(in_block, lambda i, j: (i, j))],
        out_specs=pl.BlockSpec(out_block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, kw), word_dt),
        interpret=interpret,
        name="reloc_decode_rows",
    )

    def reloc_decode(words):
        out = call(words)
        return out if dt.itemsize == 4 else from_words(out, dt, k)

    fn = jax.jit(reloc_decode)
    _CACHE.put(key, fn)
    return fn


def decode_rows(rows, *, nbytes: int, dtype, interpret: bool = False):
    """A delivered ``(m, W/4)`` uint32 word block → ``(m, k)`` typed
    rows.

    The manifest's row width (``nbytes``) and dtype are static kernel
    params: the class padding is trimmed and the words reinterpreted —
    the receiver-side inverse of :func:`encode_pack`.
    """
    rows = jnp.asarray(rows, jnp.uint32)
    m, wq = int(rows.shape[0]), int(rows.shape[1])
    fn = _decode_call(m, wq, int(nbytes), np.dtype(dtype), interpret)
    return fn(rows)
