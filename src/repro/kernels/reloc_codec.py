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
  addresses — and each output chunk is fetched as two super-rows and
  realigned in VMEM with a dynamic lane rotate.  Rows narrower than a
  chunk share one, rows wider span several.  Empty slots are never
  written: the output aliases a zero buffer.
* :func:`encode_pack` — the same gather for a typed chunk matrix: its
  word rows (padded to the row class) are the arena.
* :func:`decode_rows` — received word rows → the manifest's dtype: trim
  the class padding and reinterpret, in row tiles.

All kernels run under ``interpret=True`` on CPU (the parity target,
bit-identical to :mod:`repro.kernels.ref`); the compiled path is the
TPU execution target.

Jitted kernel instances are cached per static shape in a bounded
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
def _gather_kernel(off_ref, len_ref, arena, _zeros, out, scr, ob, sem, *,
                   slots: int, wq: int):
    """One grid step packs one (src, dest) pair's ``slots`` rows.

    ``off_ref``/``len_ref`` (SMEM, ``(1, 1, slots)``): word offset and
    live word count of each slot's row in ``arena`` (HBM super-rows).
    ``out`` (HBM) aliases a zero buffer, so only chunks holding live
    words are written."""
    p = pl.program_id(0)
    rows = arena.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

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

    def emit(chunk):
        cp = pltpu.make_async_copy(ob, out.at[p, pl.ds(chunk, 1)], sem)
        cp.start()
        cp.wait()

    if wq >= _LANES:
        nsub = wq // _LANES

        def slot(r, carry):
            off = off_ref[0, 0, r]
            n = jnp.minimum(len_ref[0, 0, r], wq)

            def sub(j, carry):
                @pl.when(n > j * _LANES)
                def _():
                    ob[0] = fetch(off + j * _LANES, n - j * _LANES)
                    emit(r * nsub + j)
                return carry

            return jax.lax.fori_loop(0, nsub, sub, carry)

        jax.lax.fori_loop(0, slots, slot, 0)
        return

    per = min(_LANES // wq, slots)      # rows sharing one output chunk

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
        emit(g)
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
    tab = pl.BlockSpec((1, 1, slots), lambda p: (p, 0, 0),
                       memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        functools.partial(_gather_kernel, slots=slots, wq=width // 4),
        grid=(pairs,),
        in_specs=[tab, tab, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((pairs, chunks, 1, _LANES),
                                       jnp.uint32),
        scratch_shapes=[pltpu.VMEM((2, 1, _LANES), jnp.uint32),
                        pltpu.VMEM((1, 1, _LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={3: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="reloc_pack_rows",
    )

    def run(arena, offsets, n_live):
        zeros = jnp.zeros((pairs, chunks, 1, _LANES), jnp.uint32)
        return call(offsets.reshape(pairs, 1, slots),
                    n_live.reshape(pairs, 1, slots), arena, zeros)

    fn = jax.jit(run)
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
    return fn(words, jnp.asarray(offsets, jnp.int32),
              jnp.asarray(widths, jnp.int32))


def _pack_rows_call(pairs: int, slots: int, width: int, n: int,
                    interpret: bool):
    key = ("pack", pairs, slots, width, n, interpret)
    fn = _CACHE.get(key)
    if fn is None:
        wq = width // 4
        gather = _gather_call(pairs, slots, width, _arena_rows(n, wq),
                              interpret)
        fn = jax.jit(lambda words, offsets, widths: gather(
            _arena(words, wq), offsets, _live_words(widths, wq)))
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

    def run(mat, idx, widths):
        words = jnp.pad(to_words(mat), ((0, 0), (0, wq - kw)))
        offsets = jnp.clip(idx, 0, m - 1) * wq
        return gather(_arena(words.reshape(-1), wq), offsets,
                      _live_words(widths, wq))

    fn = jax.jit(run)
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

    def run(words):
        out = call(words)
        return out if dt.itemsize == 4 else from_words(out, dt, k)

    fn = jax.jit(run)
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
