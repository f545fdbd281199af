"""Jit-resident lifeline steal loop — the device-side GLB hot path.

The host :meth:`~repro.core.glb.GlobalLoadBalancer.steal_pass` costs one
host round-trip *per steal*: Python BFS, numpy loads, a
``CollectiveMoveManager`` window each.  This module closes the ROADMAP's
"device-side steal path" item: the whole K-round steal loop runs inside
**one** jitted SPMD program —

* **psum'd outstanding-work counters** — each shard contributes its
  valid-row count through a one-hot ``lax.psum``, so every shard holds
  the full per-place load vector (the teamed cost exchange, on device);
* **lifeline-masked victim selection** — the host policy's BFS candidate
  order is precomputed per thief (:func:`steal_candidates`) and baked in
  as a static table; victim selection is a masked first-match over it;
* **masked ``all_to_all`` hand-off** — each round's move matrix is
  applied with :func:`~repro.core.glb.spmd_rebalance` (capacity-masked
  ``lax.all_to_all`` via ``spmd_relocate``), then receive slots compact
  back to the shard's fixed buffer;
* **device-side termination detection** — a ``lax.while_loop`` exits
  when a whole round acquires nothing (and reports whether every live
  place is idle — the psum'd termination test).

The plan (:func:`spmd_steal_plan`) mirrors the host ``steal_pass``
semantics *exactly*: thieves are visited in place order, idleness is
judged on round-start loads, victims on live loads (earlier thieves in
the same round update them), and the serve count is
``max(1, floor(surplus * steal_ratio))`` clamped to the surplus — so
the final per-place *load vector* (and every steal statistic) matches
the host policy exactly (``GLBConfig(random_steal_attempts=0)``, the
deterministic lifeline-only policy; ``steal_ratio`` should be exactly
representable in float32, e.g. the default 0.5, for bit-equal counts).
Which *specific* entries land where may differ between the two paths:
count moves let the library pick the entries on both sides — the host
takes them in range order along the steal chains, the device realizes
the same net flow with a keep-first transport.

The SPMD body is mesh-agnostic: :func:`run_device_steal` drives it with
``jax.vmap(axis_name=...)`` — one device, the deployment-faithful
emulation — while the same body runs unchanged under ``shard_map`` on a
real mesh (see the slow-tier SPMD test).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..compat import axis_size
from . import telemetry
from .distribution import LongRange

__all__ = [
    "steal_candidates",
    "spmd_steal_plan",
    "spmd_steal_step",
    "spmd_steal_loop",
    "run_device_steal",
]


def steal_candidates(lifelines: dict[int, tuple[int, ...]], n: int
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Per-thief victim candidate order + hop depth, as static tables.

    Row ``t`` lists the places a thief at ``t`` would try, in exactly
    the host ``GlobalLoadBalancer.steal`` order — both consume
    :func:`repro.core.glb.lifeline_bfs`, the single definition the
    host/device parity rests on.  Padded with -1; places absent from
    ``lifelines`` (evicted) get all-pad rows and never appear as
    candidates.
    """
    from .glb import lifeline_bfs

    k = max(n - 1, 1)
    cand = np.full((n, k), -1, np.int32)
    hops = np.zeros((n, k), np.int32)
    for t in range(n):
        if t not in lifelines:
            continue
        for j, (v, h) in enumerate(lifeline_bfs(lifelines, t)):
            cand[t, j] = v
            hops[t, j] = h
    return cand, hops


def spmd_steal_plan(loads, *, candidates, hops, alive, steal_ratio: float,
                    min_keep: int, idle_threshold: int, capacity: int):
    """One steal round's move plan, traced from the (n,) load vector.

    Deterministic mirror of one host ``steal_pass``: a ``fori_loop``
    visits thieves in place order; each idle live thief picks the first
    lifeline candidate whose *live* load exceeds ``min_keep`` and steals
    ``max(1, floor(surplus * steal_ratio))`` (clamped to the surplus and
    to the thief's free buffer slots — the latter never binds when the
    per-shard capacity covers the global entry count).

    Returns ``(loads_after, move_matrix, attempted, served, stolen,
    hop_sum)``; every shard computes the identical plan from the psum'd
    loads, so no extra exchange is needed to agree on it.
    """
    n = loads.shape[0]
    loads0 = loads
    ratio = jnp.float32(steal_ratio)

    def thief(i, carry):
        loads, moves, att, served, stolen, hop_sum = carry
        idle = alive[i] & (loads0[i] <= idle_threshold)
        ci = candidates[i]                       # (n-1,) BFS order, -1 pad
        vload = loads[jnp.clip(ci, 0, n - 1)]
        can = (ci >= 0) & (vload > min_keep)
        j = jnp.argmax(can)                      # first eligible candidate
        found = idle & jnp.any(can)
        victim = jnp.clip(ci[j], 0, n - 1)
        surplus = loads[victim] - min_keep
        cnt = jnp.maximum(
            1, jnp.floor(surplus.astype(jnp.float32) * ratio)
            .astype(jnp.int32))
        cnt = jnp.minimum(cnt, jnp.maximum(surplus, 0))
        cnt = jnp.minimum(cnt, capacity - loads[i])   # buffer headroom
        cnt = jnp.where(found, cnt, 0)
        moves = moves.at[victim, i].add(cnt)
        loads = loads.at[victim].add(-cnt).at[i].add(cnt)
        return (loads, moves, att + idle.astype(jnp.int32),
                served + (cnt > 0).astype(jnp.int32), stolen + cnt,
                hop_sum + jnp.where(cnt > 0, hops[i, j], 0))

    init = (loads, jnp.zeros((n, n), jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0))
    return jax.lax.fori_loop(0, n, thief, init)


def _psum_loads(count, me, n, axis_name):
    """The outstanding-work counter exchange: every shard contributes
    its local row count and ends up with the full (n,) load vector via
    one one-hot psum."""
    return jax.lax.psum(
        jax.nn.one_hot(me, n, dtype=jnp.int32) * count, axis_name)


def _compact_prefix(x, valid, gids):
    """Establish the prefix invariant once per loop entry: valid rows
    move to slots [0, count) in original order (cumsum rank + masked
    scatter).  Buffers produced by :func:`run_device_steal` are already
    prefix-packed; this makes the SPMD entry points safe for arbitrary
    masks too."""
    S = x.shape[0]
    vmask = valid.astype(bool)
    rank = jnp.cumsum(vmask.astype(jnp.int32)) - 1
    slot = jnp.where(vmask, rank, S)              # S = drop sentinel
    nx = jnp.zeros((S + 1,) + x.shape[1:], x.dtype) \
        .at[slot].set(x, mode="drop")[:-1]
    ng = jnp.full((S + 1,), -1, gids.dtype).at[slot].set(
        gids, mode="drop")[:-1]
    return nx, ng, jnp.sum(vmask.astype(jnp.int32))


def _ship_hop(x, gids, count, ship, *, axis_name: str):
    """One masked ``all_to_all`` hand-off of ``ship[me]`` rows per
    destination, under the *prefix invariant*: every shard's valid rows
    occupy buffer slots ``[0, count)``.

    Because valid rows are a contiguous prefix, both the send-buffer
    pack and the receive-side compaction reduce to cumsum/searchsorted
    *gathers* — no scatter, no sort — which is what keeps the loop body
    cheap enough to beat the host path even on the CPU backend.  The
    first ``sum(ship[me])`` rows leave (grouped by destination, in rank
    order — the device analogue of the host count move picking entries
    in range order); kept rows shift to the front; received rows append
    in source-shard order.  Returns ``(x, gids, new_count)`` with buffer
    shapes unchanged.
    """
    n = ship.shape[0]
    S = x.shape[0]
    me = jax.lax.axis_index(axis_name)
    k = jnp.arange(S, dtype=jnp.int32)
    tail1 = (1,) * (x.ndim - 1)

    row = ship[me]                                  # (n,) outgoing counts
    bounds = jnp.cumsum(row)
    total_out = bounds[-1]
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            bounds[:-1].astype(jnp.int32)])
    # send buffer (n, S): slot (d, r) <- outgoing row offs[d] + r
    d = jnp.repeat(jnp.arange(n, dtype=jnp.int32), S)
    r = jnp.tile(k, n)
    src = jnp.clip(offs[d] + r, 0, S - 1)
    send_mask = r < row[d]
    sx = jnp.where(send_mask.reshape((n * S,) + tail1), x[src],
                   0).reshape((n, S) + x.shape[1:])
    sg = jnp.where(send_mask, gids[src], -1).reshape(n, S)
    rx = jax.lax.all_to_all(sx, axis_name, 0, 0, tiled=False)
    rg = jax.lax.all_to_all(sg, axis_name, 0, 0, tiled=False)
    rx = rx.reshape((n * S,) + x.shape[1:])
    rg = rg.reshape(n * S)

    rc = ship[:, me]                                # (n,) incoming counts
    crc = jnp.cumsum(rc)
    total_in = crc[-1]
    crc_prev = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                crc[:-1].astype(jnp.int32)])
    kept = count - total_out
    new_count = kept + total_in
    # slot k: kept rows first (shifted down past the departed prefix),
    # then each source block's contiguous received prefix
    j = k - kept
    b = jnp.clip(jnp.searchsorted(crc, j, side="right").astype(jnp.int32),
                 0, n - 1)
    rsrc = jnp.clip(b * S + (j - crc_prev[b]), 0, n * S - 1)
    from_kept = k < kept
    live = k < new_count
    keep_src = jnp.clip(total_out + k, 0, S - 1)
    nx = jnp.where(from_kept.reshape((S,) + tail1), x[keep_src], rx[rsrc])
    ng = jnp.where(from_kept, gids[keep_src], rg[rsrc])
    nx = jnp.where(live.reshape((S,) + tail1), nx, 0)
    ng = jnp.where(live, ng, -1)
    return nx, ng, new_count


def _transport(before, after):
    """(n, n) row-flow matrix realizing the load change ``before →
    after`` with minimal shuffling: every shard keeps
    ``min(before, after)`` rows in place, and the residual surpluses
    route to the residual deficits by the northwest-corner rule.  A
    shard is never both surplus and deficit, so the result has a zero
    diagonal — only real movement reaches the wire."""
    keep = jnp.minimum(before, after)
    supply = before - keep
    demand = after - keep
    cum_s = jnp.cumsum(supply)
    cum_d = jnp.cumsum(demand)
    lo = jnp.maximum((cum_s - supply)[:, None], (cum_d - demand)[None, :])
    hi = jnp.minimum(cum_s[:, None], cum_d[None, :])
    return jnp.maximum(hi - lo, 0).astype(jnp.int32)


def _apply_moves(x, gids, count, moves, loads, *, axis_name: str):
    """Execute a round's (n, n) move matrix with masked ``all_to_all``
    hand-offs, honoring intra-round steal *chains*.

    The host pass is sequential: thief B may steal entries its victim
    only received from thief A's steal moments earlier, so the move
    matrix can ask a shard to ship rows it does not hold yet.  One
    simultaneous collective cannot satisfy that — instead the matrix is
    resolved by a short inner loop: every iteration each shard ships
    what its current inventory covers (greedy, in destination order) and
    the remainder waits for the next hop.  Inventory evolution is a
    deterministic function of the matrix and the psum'd loads, so every
    shard simulates the *global* schedule locally — the inner loop costs
    one ``all_to_all`` per chain hop and zero extra exchanges.  Chains
    are dependency-ordered (an edge only ever waits on strictly earlier
    edges), so at most n-1 hops resolve everything.
    """
    n = loads.shape[0]

    def cond(c):
        x, gids, count, remaining, inv, k = c
        return (remaining.sum() > 0) & (k < n)

    def hop(c):
        x, gids, count, remaining, inv, k = c
        cum = jnp.cumsum(remaining, axis=1)
        prev = jnp.concatenate(
            [jnp.zeros((n, 1), jnp.int32), cum[:, :-1]], axis=1)
        ship = jnp.clip(jnp.minimum(cum, inv[:, None]) - prev, 0, remaining)
        x, gids, count = _ship_hop(x, gids, count, ship,
                                   axis_name=axis_name)
        inv = inv - ship.sum(axis=1) + ship.sum(axis=0)
        return (x, gids, count, remaining - ship, inv, k + 1)

    x, gids, count, remaining, inv, _ = jax.lax.while_loop(
        cond, hop, (x, gids, count, jnp.asarray(moves, jnp.int32), loads,
                    jnp.int32(0)))
    return x, gids, count


def spmd_steal_step(x, valid, gids, *, axis_name: str, candidates, hops,
                    alive, steal_ratio: float, min_keep: int,
                    idle_threshold: int):
    """One steal round inside a jitted shard_map/vmap body: psum the
    outstanding-work counters, plan (lifeline-masked victim selection),
    and hand off rows with masked ``all_to_all`` exchanges (one per
    intra-round chain hop, see :func:`_apply_moves`).

    ``x``/``valid``/``gids`` are the shard's fixed-size row buffer
    (``S`` slots), its validity mask, and the rows' global entry ids.
    Returns ``(x, valid, gids, info)`` with shapes unchanged (rows
    compact to a prefix of the ``S``-slot buffer) — so the step can
    iterate inside ``lax.while_loop``.
    """
    n = axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    x, gids, count = _compact_prefix(x, gids=gids, valid=valid)
    loads = _psum_loads(count, me, n, axis_name)
    x, gids, count, info = _steal_round(
        x, gids, count, loads, axis_name=axis_name, candidates=candidates,
        hops=hops, alive=alive, steal_ratio=steal_ratio, min_keep=min_keep,
        idle_threshold=idle_threshold)
    return x, jnp.arange(x.shape[0], dtype=jnp.int32) < count, gids, info


def _steal_round(x, gids, count, loads, *, axis_name, candidates, hops,
                 alive, steal_ratio, min_keep, idle_threshold):
    """Plan + hand-off for one round, on prefix-packed buffers."""
    S = x.shape[0]
    loads_after, moves, att, served, stolen, hop_sum = spmd_steal_plan(
        loads, candidates=candidates, hops=hops, alive=alive,
        steal_ratio=steal_ratio, min_keep=min_keep,
        idle_threshold=idle_threshold, capacity=S)
    x, gids, count = _apply_moves(x, gids, count, moves, loads,
                                  axis_name=axis_name)
    info = {"moved": moves.sum(), "loads": loads_after, "attempted": att,
            "served": served, "stolen": stolen, "hops": hop_sum}
    return x, gids, count, info


def spmd_steal_loop(x, valid, gids, *, axis_name: str, candidates, hops,
                    alive, steal_ratio: float, min_keep: int,
                    idle_threshold: int, max_rounds: int,
                    assume_prefix: bool = False):
    """K steal rounds with zero host round-trips: a ``lax.while_loop``
    of :func:`spmd_steal_step` that exits as soon as a whole round
    acquires nothing (the host loop's ``while steal_pass() > 0``).

    Returns a dict with the final ``x``/``valid``/``gids`` buffers,
    ``rounds`` executed, aggregate steal stats, and ``terminated`` —
    the psum'd termination test (nothing moved and every live place
    idle)."""
    gids = gids.astype(jnp.int32)
    zero = jnp.int32(0)
    n = axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    if assume_prefix:
        # caller guarantees valid rows occupy slots [0, count) — e.g.
        # run_device_steal packs them that way — so the compaction
        # scatter is skipped entirely
        count = jnp.sum(valid.astype(jnp.int32))
        gids = jnp.where(jnp.arange(x.shape[0]) < count, gids, -1)
    else:
        x, gids, count = _compact_prefix(x, valid, gids)
    loads0 = _psum_loads(count, me, n, axis_name)

    # The K rounds iterate on the psum'd counters only: each round's
    # plan is a pure function of the load vector, so the whole
    # convergence loop is (n,)-vector arithmetic — no data motion, no
    # host round-trip.  Rows are fungible (the host count move "picks
    # the entries" too), so the rounds' cumulative effect on *data* is
    # realized afterwards by one transport hand-off.
    def cond(c):
        loads, r, moved_last, att, served, stolen, hop_sum = c
        return (r < max_rounds) & (moved_last != 0)

    def body(c):
        loads, r, _, att, served, stolen, hop_sum = c
        loads, moves, a, s, st_, h = spmd_steal_plan(
            loads, candidates=candidates, hops=hops, alive=alive,
            steal_ratio=steal_ratio, min_keep=min_keep,
            idle_threshold=idle_threshold, capacity=x.shape[0])
        return (loads, r + 1, moves.sum(), att + a, served + s,
                stolen + st_, hop_sum + h)

    loads, r, moved_last, att, served, stolen, hop_sum = \
        jax.lax.while_loop(
            cond, body, (loads0, zero, jnp.int32(1), zero, zero, zero,
                         zero))
    # one masked all_to_all realizes the rounds' net row flow: keep
    # min(before, after) rows in place, route the residual surpluses to
    # the residual deficits (northwest-corner transport — diagonal-free
    # since a shard is never both surplus and deficit)
    ship = _transport(loads0, loads)
    x, gids, count = _ship_hop(x, gids, count, ship, axis_name=axis_name)
    all_idle = jnp.all(jnp.where(alive, loads <= idle_threshold, True))
    valid = jnp.arange(x.shape[0], dtype=jnp.int32) < count
    return {
        "x": x, "valid": valid, "gids": gids, "rounds": r,
        "attempted": att, "served": served, "stolen": stolen,
        "hops": hop_sum, "terminated": (moved_last == 0) & all_idle,
    }


# ---------------------------------------------------------------------------
# Host wrapper: DistArray -> device buffers -> jit loop -> DistArray
# ---------------------------------------------------------------------------
# bounded like DeviceTransport._fns: every (n, S, config) key is a
# compiled program, and elastic runs change n per resize
def _make_loop_cache():
    import os

    from ..kernels.reloc_codec import LRUCache

    return LRUCache(int(os.environ.get("REPRO_KERNEL_CACHE_CAP", "64")))


_LOOP_CACHE = _make_loop_cache()


def _loop_fn(n: int, S: int, cand_b: bytes, hops_b: bytes,
             alive_b: bytes, steal_ratio: float, min_keep: int,
             idle_threshold: int, max_rounds: int):
    """Jitted vmap runner over payload buffers, cached per static
    configuration so repeated steal loops (benchmark iterations,
    successive GLB calls) reuse one compilation.  The payload slot ``x``
    is shape-polymorphic (jit retraces per buffer shape): the id-mode
    caller passes the id column, the device-transport caller passes the
    codec's fixed-width byte rows."""
    key = (n, S, cand_b, hops_b, alive_b, steal_ratio, min_keep,
           idle_threshold, max_rounds)
    fn = _LOOP_CACHE.get(key)
    if fn is None:
        k = max(n - 1, 1)
        candidates = jnp.asarray(
            np.frombuffer(cand_b, np.int32).reshape(n, k))
        hops = jnp.asarray(np.frombuffer(hops_b, np.int32).reshape(n, k))
        alive = jnp.asarray(np.frombuffer(alive_b, np.bool_))

        def per_shard(x, valid, gids):
            return spmd_steal_loop(
                x, valid, gids, axis_name="places",
                candidates=candidates, hops=hops, alive=alive,
                steal_ratio=steal_ratio, min_keep=min_keep,
                idle_threshold=idle_threshold, max_rounds=max_rounds,
                assume_prefix=True)

        fn = jax.jit(jax.vmap(per_shard, axis_name="places"))
        _LOOP_CACHE.put(key, fn)
    return fn


def run_device_steal(col, lifelines: dict[int, tuple[int, ...]],
                     alive: Sequence[int], *, steal_ratio: float,
                     min_keep: int, idle_threshold: int,
                     max_rounds: int = 12,
                     capacity: int | None = None,
                     ship_rows: bool = False) -> dict:
    """Run the jit-resident steal loop over a tracked :class:`DistArray`.

    Packs each place's entries into a fixed ``capacity``-slot device
    buffer, executes all rounds in **one** jitted call, then rebuilds
    the per-place chunks and reconciles the tracked distribution
    **once** at the end (a single ``update_dist``, versus one per host
    steal).

    Two data planes, selected by ``ship_rows`` (the GLB maps its
    ``GLBConfig(transport=...)`` onto it):

    * ``ship_rows=False`` — the *host* data plane: entry ids are the
      relocated device payload; the rows themselves are materialized
      host-side from the original chunks by id (the host memory bounce
      a real deployment would pay), so any dtype — float64 included —
      round-trips bit-exactly.
    * ``ship_rows=True`` — the *device* data plane: each row is encoded
      to fixed-width 32-bit words by the collection's row codec and
      rides the loop's masked ``all_to_all`` payload slot next to its
      id; the receiver decodes bit-exactly (uint32 words are
      dtype-safe without x64) and no host
      materialization happens.  Both planes run the identical jitted
      plan, so they produce *bit-identical* final collection state.

    ``capacity`` defaults to the global entry count — the always-safe
    bound under which the plan's buffer clamp never binds, so the final
    per-place load vector equals the host ``steal_pass`` policy's
    exactly.
    """
    # host-side wrapper span only: the jitted loop body itself is never
    # traced (tracing inside jit would bake timestamps into the program)
    with telemetry.span("glb.device_loop", ship_rows=ship_rows) as sp:
        res = _run_device_steal(
            col, lifelines, alive, steal_ratio=steal_ratio,
            min_keep=min_keep, idle_threshold=idle_threshold,
            max_rounds=max_rounds, capacity=capacity, ship_rows=ship_rows)
        if sp:
            sp.set(rounds=res["rounds"], stolen=res["stolen"],
                   capacity=res["capacity"])
        return res


def _run_device_steal(col, lifelines, alive, *, steal_ratio, min_keep,
                      idle_threshold, max_rounds, capacity,
                      ship_rows) -> dict:
    members = tuple(col.group.members)
    n = len(members)
    empty = {"rounds": 0, "attempted": 0, "served": 0, "stolen": 0,
             "hops": 0, "bytes_moved": 0, "terminated": True,
             "capacity": 0}
    if n < 2:
        return empty
    per_place = [col.to_local_matrix(p) for p in members]
    sizes = [len(idx) for _, idx in per_place]
    total = sum(sizes)
    if total == 0:
        return empty
    first = next(rows for rows, idx in per_place if len(idx))
    trail = tuple(np.asarray(first).shape[1:])
    orig_dtype = np.asarray(first).dtype
    row_nbytes = int(np.prod(trail, dtype=np.int64) * orig_dtype.itemsize) \
        if trail else orig_dtype.itemsize
    S = int(capacity) if capacity is not None else total
    if max(sizes) > S:
        raise ValueError(
            f"capacity {S} < largest resident shard {max(sizes)}")
    valid = np.zeros((n, S), np.bool_)
    gids = np.full((n, S), -1, np.int32)
    for i, (rows, idx) in enumerate(per_place):
        m = len(idx)
        if m == 0:
            continue
        if idx.max() >= np.iinfo(np.int32).max:
            raise ValueError("global indices exceed the int32 id payload")
        valid[i, :m] = True
        gids[i, :m] = idx
    # on a fused codec backend the relocated rows stay on device: the
    # collection's decode fast path trims + reinterprets them in-kernel
    # and only the typed result crosses to host
    fused_rows = False
    if ship_rows:
        from ..kernels import ops
        from ..kernels.reloc_codec import jax_safe_dtype, to_words

        fused_rows = ops.resolve_backend() in ("pallas",
                                               "pallas_interpret") \
            and jax_safe_dtype(orig_dtype)
        # codec rows ride the all_to_all payload slot as 32-bit words:
        # the device word layout where the receiver decodes on device,
        # the little-endian view of the host bytes where it decodes on
        # host (via the transport's donation probe: DistArray hands back
        # zero-copy byte views instead of tobytes copies)
        from .transport import _encode_rows

        x = np.zeros((n, S, -(-row_nbytes // 4)), np.uint32)
        for i, (rows, idx) in enumerate(per_place):
            m = len(idx)
            if not m:
                continue
            if fused_rows:
                x[i, :m] = to_words(np.asarray(rows).reshape(m, -1))
            else:
                u8, _ = _encode_rows(
                    col, (LongRange(0, m), np.asarray(rows)))
                x[i, :m] = np.pad(u8, ((0, 0), (0, (-row_nbytes) % 4))) \
                    .view(np.uint32)
    else:
        # the id column doubles as the payload for the host data plane
        x = np.where(valid, gids, 0).astype(np.int32)[:, :, None]
    cand, hops = steal_candidates(lifelines, n)
    alive_mask = np.zeros(n, np.bool_)
    alive_mask[list(alive)] = True
    fn = _loop_fn(n, S, cand.tobytes(), hops.tobytes(),
                  alive_mask.tobytes(), float(steal_ratio), int(min_keep),
                  int(idle_threshold), int(max_rounds))
    dev_out = fn(x, valid, gids)
    out = {k: (v if (fused_rows and k == "x") else np.asarray(v))
           for k, v in dev_out.items()}

    # the plan is replicated — every shard reports identical stats
    stolen = int(out["stolen"][0])
    nvalid, ngids = out["valid"], out["gids"]
    assert int(nvalid.sum()) == total, "device steal lost rows"
    if not ship_rows:
        # host-side id -> row lookup over the original chunks (dtype-exact)
        all_rows = np.concatenate([np.asarray(rows)
                                   for rows, idx in per_place if len(idx)],
                                  axis=0)
        all_idx = np.concatenate([idx for _, idx in per_place if len(idx)])
        order = np.argsort(all_idx, kind="stable")
        all_rows, all_idx = all_rows[order], all_idx[order]
    # rebuild the chunks: each place's relocated ids sorted, split into
    # consecutive runs; one update_dist reconciles the tracked
    # distribution for the whole loop
    for p in members:
        col.handle(p).chunks.clear()
    for i, p in enumerate(members):
        v = nvalid[i]
        if not v.any():
            continue
        g = ngids[i][v].astype(np.int64)
        order = np.argsort(g, kind="stable")
        g = g[order]
        if ship_rows:
            # decode the relocated byte rows directly — the rows arrived
            # with their ids, no host materialization needed
            from .collections import _dtype_token
            blk = out["x"][i][v][order]
            if isinstance(blk, np.ndarray):
                blk = np.ascontiguousarray(blk)
            _, r = col.decode_rows(
                blk,
                ("chunk", LongRange(0, len(g)), _dtype_token(orig_dtype),
                 trail))
        else:
            r = all_rows[np.searchsorted(all_idx, g)]
        splits = np.nonzero(np.diff(g) != 1)[0] + 1
        for grun, rrun in zip(np.split(g, splits), np.split(r, splits)):
            col.handle(p).add_chunk(
                LongRange(int(grun[0]), int(grun[-1]) + 1), rrun)
    if col.track:
        col.update_dist()
    return {
        "rounds": int(out["rounds"][0]),
        "attempted": int(out["attempted"][0]),
        "served": int(out["served"][0]),
        "stolen": stolen,
        "hops": int(out["hops"][0]),
        "bytes_moved": stolen * row_nbytes,
        "terminated": bool(out["terminated"][0]),
        "capacity": S,
    }
