#!/usr/bin/env python3
"""Chip smoke test: drive the system's main paths once on a TPU.

    python chip_smoke.py [--seed N]       # one chip: three phases
    python chip_smoke.py --chips 4 [...]  # four chips: the mesh phase only

One chip runs, in one process:

* ``relocation`` — a ``DistArray`` over 8 places, 32768 rows of 1 KiB
  (f32 × 256) per place, sheds a hot shard off place 0 through three
  ``CollectiveMoveManager(transport="device")`` windows of at most 4096
  rows per (src, dest) pair; the final entries and tracked distribution
  must be bit-identical to the same windows run through
  ``HostTransport``.
* ``glb_device_loop`` — ``GlobalLoadBalancer(device_loop=True)`` on a
  device transport (rows ride the steal loop's ``all_to_all``) over a
  hot shard; its final load vector must match the host ``steal_pass``
  loop and every row must arrive bit-exact.
* ``serving`` — ``RealDecodeSim`` over ``DecodeEngine(qwen2_1_5b)`` at
  its published widths with bf16 parameters, 4 replicas on the chip,
  KV migration windows on the device transport; it must lose no
  sequence, complete at least one, and keep migrated KV on the device.

``--chips 4`` runs the SPMD steal loop and ``spmd_team_reduce`` under
``shard_map`` on a 4-chip mesh (one place per chip) and checks both
against the one-device ``vmap`` run and the host reference.

Each phase prints one JSON line (wall time, compile count and seconds,
``peak_bytes_in_use``, its checks); the last line is
``{"ok": true, "device": {...}}`` only when every phase passed.  Data
and parameters come from ``--seed``.  There is no CPU fallback: the
script exits non-zero without a TPU, and refuses a
``REPRO_KERNEL_BACKEND`` that forces interpret mode or XLA.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


# ---------------------------------------------------------------------------
# phases (sizes are arguments so a CPU rehearsal can shrink them)
# ---------------------------------------------------------------------------
def relocation(seed: int, *, places: int = 8, rows: int = 32768,
               cols: int = 256, windows: int = 3, hot: int = 4096,
               background: int = 1024) -> dict:
    """Hot-shard windows, device transport vs host transport."""
    from repro.core import (CollectiveMoveManager, DistArray, HostTransport,
                            LongRange, PlaceGroup, make_transport)

    data = np.random.default_rng(seed).standard_normal(
        (places * rows, cols), dtype=np.float32)

    def run(transport):
        g = PlaceGroup(places)
        col = DistArray(g, track=True)
        for p in g.members:
            col.add_chunk(p, LongRange(p * rows, (p + 1) * rows),
                          data[p * rows:(p + 1) * rows].copy())
        mm = CollectiveMoveManager(g, transport=transport)
        per_window = []
        for w in range(windows):
            # place 0 sheds its hot shard; the others shuffle a little
            for p in g.members:
                col.move_at_sync_count(p, hot if p == 0 else background,
                                       (p + 1 + w) % places, mm)
            mm.sync()
            per_window.append(mm.last_transport_stats)
        col.update_dist()
        return col, per_window

    dev, dev_stats = run(make_transport("device"))
    host, _ = run(HostTransport())
    for p in range(places):
        rd, idd = dev.to_local_matrix(p)
        rh, idh = host.to_local_matrix(p)
        assert dev.ranges(p) == host.ranges(p), f"ranges differ at {p}"
        assert np.array_equal(idd, idh), f"indices differ at place {p}"
        assert rd.dtype == rh.dtype and rd.tobytes() == rh.tobytes(), \
            f"row bytes differ at place {p}"
    assert list(dev.get_distribution().items()) \
        == list(host.get_distribution().items()), "distributions differ"
    assert dev.global_size() == places * rows
    backends = sorted({s.codec_backend for s in dev_stats})
    return {
        "codec_backend": ",".join(backends),
        "windows": windows,
        "rows_moved": [s.rows for s in dev_stats],
        "wire_bytes": [s.wire_bytes for s in dev_stats],
        "pad_waste_bytes": [s.pad_waste_bytes for s in dev_stats],
        "exchanges": [s.exchanges for s in dev_stats],
        "bitwise_parity": True,
    }


def glb_device_loop(seed: int, *, places: int = 8, rows: int = 16384,
                    cols: int = 256) -> dict:
    """Jit-resident steal loop shipping rows vs the host steal loop."""
    from repro.core import (DistArray, DistArrayWorkload, GLBConfig,
                            GlobalLoadBalancer, LongRange, PlaceGroup)
    from repro.kernels import ops

    data = np.random.default_rng(seed + 1).standard_normal(
        (rows, cols), dtype=np.float32)

    def run(device_loop, transport):
        g = PlaceGroup(places)
        col = DistArray(g, track=True)
        col.add_chunk(0, LongRange(0, rows), data.copy())
        for p in g.members:
            col.handle(p)
        glb = GlobalLoadBalancer(
            g, DistArrayWorkload(col),
            GLBConfig(lifeline="hypercube", random_steal_attempts=0,
                      transport=transport), device_loop=device_loop)
        return col, glb.steal_loop(max_rounds=12)

    dev, res_d = run(True, "device")
    host, res_h = run(False, "host")
    loads_d = [dev.local_size(p) for p in range(places)]
    loads_h = [host.local_size(p) for p in range(places)]
    assert loads_d == loads_h, f"loads differ: {loads_d} vs {loads_h}"
    assert res_d["stolen"] == res_h["stolen"]
    assert dev.global_size() == rows
    for p in range(places):
        got, idx = dev.to_local_matrix(p)
        if len(idx):
            assert got.tobytes() == data[idx].tobytes(), \
                f"rows at place {p} not bit-exact"
    return {"codec_backend": ops.resolve_backend(), "loads": loads_d,
            "stolen": res_d["stolen"], "rounds": res_d["rounds"],
            "load_parity": True}


def serving(seed: int, *, cfg=None, s_cache: int = 2048, max_batch: int = 8,
            replicas: int = 4, preload: int = 12, rounds: int = 12,
            glb_period: int = 4) -> dict:
    """Full-width decode replicas whose KV migrates on the device."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.serving import DecodeEngine, RealDecodeSim

    if cfg is None:
        # published widths; parameters in bf16 as the checkpoint ships
        cfg = dataclasses.replace(get_config("qwen2_1_5b"),
                                  param_dtype="bfloat16")
    engine = DecodeEngine(cfg, s_cache=s_cache, max_batch=max_batch,
                          seed=seed)
    sim = RealDecodeSim(n_replicas=replicas, preload=(0, preload),
                        glb_period=glb_period, transport="device",
                        arrival_rate=1.0, max_new_range=(4, 8),
                        engine=engine, seed=seed)
    d = sim.driver
    kv_windows = []
    exchange = d.transport.exchange

    def counting_exchange(group, counts, payloads):
        kv_windows.append(sum(1 for col, src, dest, _ in payloads
                              if col is d.kv and src != dest))
        return exchange(group, counts, payloads)

    d.transport.exchange = counting_exchange
    preloaded = set(d.kv.keys(0))
    sim.run(rounds)
    migrated = [k for p in d.group.members if p != 0
                for k in d.kv.keys(p) if k in preloaded]
    assert d.lost() == 0, f"{d.lost()} sequences lost"
    assert len(d.completed) >= 1, "no sequence completed"
    moving = [n for n in kv_windows if n]
    assert len(moving) >= 2, f"KV moved in {len(moving)} windows"
    assert migrated, "no preloaded sequence left replica 0"
    for p in d.group.members:
        assert sorted(d.seqs.keys(p)) == sorted(d.kv.keys(p))
        for v in d.kv.handle(p).values():
            assert v.on_device(), "KV left the device"
    life = d.transport.lifetime
    sample = next(iter(d.kv.handle(
        next(p for p in d.group.members if d.kv.keys(p))).values()))
    leaf = jax.tree_util.tree_leaves(sample)
    return {"model": cfg.name, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "vocab": cfg.vocab_size,
            "param_dtype": cfg.param_dtype, "s_cache": s_cache,
            "codec_backend": life.codec_backend,
            "kv_seqs_per_window": kv_windows, "migrated": len(migrated),
            "completed": len(d.completed), "lost": d.lost(),
            "tokens": sim.tokens, "kv_row_bytes": life.row_bytes,
            "wire_bytes": life.wire_bytes,
            "pad_waste_bytes": life.pad_waste_bytes,
            "exchanges": life.exchanges,
            "kv_leaf_device": str(leaf[0].devices().pop())}


class _SumReducer:
    """Additive monoid: the ``psum`` path."""

    additive = True

    def __init__(self, cols):
        self.cols = cols

    def new_reducer(self):
        return np.zeros(self.cols, np.int32)

    def reduce(self, state, rows):
        return state + np.asarray(rows).sum(axis=0, dtype=np.int32)

    def merge(self, a, b):
        return a + b


class _MaxCount:
    """Non-additive monoid: the ``all_gather`` + merge path."""

    additive = False

    def __init__(self, cols):
        self.cols = cols

    def new_reducer(self):
        return (np.full(self.cols, np.iinfo(np.int32).min, np.int32),
                np.zeros((), np.int32))

    def reduce(self, state, rows):
        rows = np.asarray(rows)
        return (np.maximum(state[0], rows.max(axis=0)),
                state[1] + np.int32(rows.shape[0]))

    def merge(self, a, b):
        import jax.numpy as jnp

        return (jnp.maximum(a[0], b[0]), a[1] + b[1])


def spmd_mesh(seed: int, *, places: int = 4, rows: int = 65536,
              cols: int = 256) -> dict:
    """Steal loop and team reduce under shard_map, one place per chip."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh, shard_map
    from repro.core import (DistArray, DistArrayWorkload, GLBConfig,
                            GlobalLoadBalancer, LongRange, PlaceGroup,
                            hypercube_lifelines, local_reduce,
                            spmd_steal_loop, spmd_team_reduce,
                            steal_candidates, team_reduce)

    devices = jax.devices()[:places]
    assert len(devices) == places, f"needs {places} devices"
    mesh = make_mesh((places,), ("places",), devices=devices)
    cand, hops = steal_candidates(hypercube_lifelines(places), places)
    candj, hopsj, alive = jnp.asarray(cand), jnp.asarray(hops), \
        jnp.ones(places, bool)
    data = np.random.default_rng(seed + 2).integers(
        -1000, 1000, (rows, cols), dtype=np.int32)

    # hot shard: every row starts on place 0; capacity covers them all
    x = np.zeros((places, rows, cols), np.int32)
    valid = np.zeros((places, rows), bool)
    gids = np.full((places, rows), -1, np.int32)
    x[0], valid[0], gids[0] = data, True, np.arange(rows)

    def body(xl, vl, gl):
        out = spmd_steal_loop(xl, vl, gl, axis_name="places",
                              candidates=candj, hops=hopsj, alive=alive,
                              steal_ratio=0.5, min_keep=1,
                              idle_threshold=0, max_rounds=12,
                              assume_prefix=True)
        return out["x"], out["valid"], out["gids"], out["stolen"]

    def per_chip(a, b, c):
        return tuple(o[None] for o in body(a[0], b[0], c[0]))

    spec = P("places")
    sharded = jax.jit(shard_map(per_chip, mesh=mesh,
                                in_specs=(spec, spec, spec),
                                out_specs=(spec, spec, spec, spec)))
    one = jax.jit(jax.vmap(body, axis_name="places"))
    ms = sharded(x, valid, gids)
    vm = one(*(jax.device_put(a, devices[0]) for a in (x, valid, gids)))
    ms = [np.asarray(a) for a in ms]
    vm = [np.asarray(a) for a in vm]
    for a, b, name in zip(ms, vm, ("x", "valid", "gids", "stolen")):
        assert np.array_equal(a.reshape(b.shape), b), \
            f"shard_map {name} differs from the one-device vmap run"
    loads_mesh = ms[1].reshape(places, rows).sum(1).tolist()
    for p in range(places):
        v = vm[1][p]
        assert np.array_equal(vm[0][p][v], data[vm[2][p][v]]), \
            f"rows at place {p} are not the rows of their ids"

    g = PlaceGroup(places)
    col = DistArray(g, track=True)
    col.add_chunk(0, LongRange(0, rows), data.copy())
    for p in g.members:
        col.handle(p)
    res = GlobalLoadBalancer(
        g, DistArrayWorkload(col),
        GLBConfig(lifeline="hypercube", random_steal_attempts=0)
    ).steal_loop(max_rounds=12)
    loads_host = [col.local_size(p) for p in g.members]
    assert loads_mesh == loads_host, (loads_mesh, loads_host)
    assert int(vm[3][0]) == res["stolen"]

    # teamed reduction over the balanced collection, both monoid paths
    reduced = {}
    for reducer in (_SumReducer(cols), _MaxCount(cols)):
        host = team_reduce(col, reducer)
        states = [local_reduce(col, p, reducer) for p in g.members]
        stacked = jax.tree_util.tree_map(
            lambda *s: np.stack([np.asarray(t) for t in s]), *states)
        red = partial(spmd_team_reduce, reducer=reducer,
                      axis_name="places")
        on_mesh = jax.jit(shard_map(
            lambda s: jax.tree_util.tree_map(
                lambda a: a[None], red(jax.tree_util.tree_map(
                    lambda a: a[0], s))),
            mesh=mesh, in_specs=(spec,), out_specs=spec))(stacked)
        on_one = jax.jit(jax.vmap(red, axis_name="places"))(
            jax.device_put(stacked, devices[0]))
        for got in (on_mesh, on_one):
            for leaf, want in zip(jax.tree_util.tree_leaves(got),
                                  jax.tree_util.tree_leaves(host)):
                leaf = np.asarray(leaf)
                for p in range(places):
                    assert np.array_equal(leaf[p], np.asarray(want)), \
                        f"{type(reducer).__name__} differs from host"
        reduced[type(reducer).__name__.strip("_")] = True
    return {"loads": loads_mesh, "stolen": res["stolen"],
            "mesh_devices": [str(d) for d in devices],
            "steal_parity": True, "team_reduce_parity": reduced}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
class _Compiles:
    """Backend compile count and seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name, fn, compiles, device) -> bool:
    c0, s0 = compiles.count, compiles.seconds
    t0 = time.perf_counter()
    try:
        detail = fn()
        ok = True
    except Exception as e:  # noqa: BLE001 - report every failing phase
        traceback.print_exc()
        detail = {"error": f"{type(e).__name__}: {e}"}
        ok = False
    line = {"phase": name, "ok": ok,
            "wall_s": time.perf_counter() - t0,
            "compiles": compiles.count - c0,
            "compile_s": compiles.seconds - s0,
            "peak_bytes_in_use": _peak_bytes(device)}
    line.update(detail)
    print(json.dumps(line), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    forced = os.environ.get("REPRO_KERNEL_BACKEND", "auto")
    if forced not in ("auto", "pallas"):
        print(f"error: REPRO_KERNEL_BACKEND={forced} would bypass the "
              "chip's kernels", file=sys.stderr)
        return 2
    from repro import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"error: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    print(json.dumps({"devices": len(devices),
                      "kind": devices[0].device_kind,
                      "jax": jax.__version__, "compile_cache": cache_dir}),
          flush=True)

    compiles = _Compiles()
    seed = args.seed
    if args.chips == 4:
        phases = [("spmd_mesh", lambda: spmd_mesh(seed))]
    else:
        phases = [("relocation", lambda: relocation(seed)),
                  ("glb_device_loop", lambda: glb_device_loop(seed)),
                  ("serving", lambda: serving(seed))]
    ok = all([run_phase(name, fn, compiles, devices[0])
              for name, fn in phases])
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
