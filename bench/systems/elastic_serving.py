"""Elastic serving: ``ElasticServingDriver`` over a ``DecodeEngine``, with
the configuration's replicas sharing the chip and KV migrating through
its transport.

The benchmark makes the weights (``bench/weights.py``) and hands them to
the engine; the program makes everything else.  A round is one
``decode_round``: every resident request on every live replica gets one
token, then the traffic-keyed balancer may start a migration window.
The population is closed: each completion is replaced by one admission
before the next round.  Served tokens are kept as the device arrays the
engine hands back and read only after the window closes.
"""
from __future__ import annotations

import dataclasses
import time
import traceback
from contextlib import contextmanager

import numpy as np

from .. import generators, weights
from ..reference import qwen2 as ref

KERNELS = ()
_FAR = 1e9      # the gap given to a served token outside the vocabulary


def model_config(config: dict):
    """The program's ``ModelConfig`` at the configuration file's sizes."""
    from repro.configs import get_config

    cfg = dataclasses.replace(
        get_config(config["program_config"]),
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"], qkv_bias=True,
        dtype=config["torch_dtype"], param_dtype=config["torch_dtype"])
    if cfg.vocab_padded != weights.vocab_padded(config):
        raise ValueError("the program pads the vocabulary differently")
    return cfg


@contextmanager
def _weights_from_benchmark(params):
    """The engine builds its weights with ``zoo.init_params``; for the
    benchmark it takes the tree the benchmark made instead."""
    import jax

    from repro.models import zoo

    made = zoo.init_params

    def given(cfg, seed=0):
        want = zoo.abstract_params(cfg)
        if jax.tree_util.tree_structure(want) != \
                jax.tree_util.tree_structure(params) or any(
                    (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                        jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(params))):
            raise ValueError("the benchmark's weight tree does not match "
                             "the program's layout")
        return params

    zoo.init_params = given
    try:
        yield
    finally:
        zoo.init_params = made


@dataclasses.dataclass
class _Request:
    start: int
    max_new: int
    first: object            # the token the engine started it with
    served: list             # one (1, 1) device array per decoded token


class System:
    def __init__(self, config: dict, mix: dict, seed: int):
        from repro.core import GLBConfig
        from repro.serving import DecodeEngine
        from repro.serving.elastic import ElasticServingDriver

        self.config, self.mix, self.seed = config, mix, seed
        s = config["serving"]
        self.cfg = model_config(config)
        with _weights_from_benchmark(weights.qwen2_params(
                config, seed, config["torch_dtype"])):
            self.engine = DecodeEngine(self.cfg, s_cache=s["s_cache"],
                                       max_batch=s["max_batch"], seed=seed)
        self.driver = ElasticServingDriver(
            s["replicas"], slots_per_replica=s["slots_per_replica"],
            glb=GLBConfig(period=s["glb_period"], policy=s["glb_policy"],
                          ema=s["glb_ema"], asynchronous=True,
                          pipeline_depth=s["pipeline_depth"]),
            engine=self.engine, transport=s["transport"])
        self.traffic = generators.load(mix, config, seed)
        self.requests: dict[int, _Request] = {}
        self.rounds = 0
        self.seen_done = 0
        self.window_done: list[int] = []
        self.failed_admissions = 0
        self.failed_rounds = 0
        self.place: dict[int, int] = {}
        self.migrated: set[int] = set()
        self.calls: list = []     # keys of each sequence, per step run
        self.token_keys: list = []

    # -- traffic -----------------------------------------------------------
    def _admit(self) -> None:
        start, max_new = self.traffic.next_request()
        sid = self.driver.admit(start, max_new)
        if sid is None:
            self.failed_admissions += 1
            return
        kv = next(self.driver.kv.handle(p)[sid]
                  for p in self.driver.group.members
                  if sid in self.driver.kv.handle(p))
        self.requests[sid] = _Request(start, max_new, kv.token, [])

    def _round(self) -> int:
        import jax

        d = self.driver
        resident = []
        for p in d.group.members:
            seqs, kvs = d.seqs.handle(p), d.kv.handle(p)
            for sid in list(kvs):
                kv, seq = kvs.get(sid), seqs.get(sid)
                if kv is not None and seq is not None:
                    resident.append((p, sid, seq, kv, seq.generated))
        with jax.profiler.TraceAnnotation("bench.decode_round"):
            info = d.decode_round()
        per_place: dict[int, list] = {}
        for p, sid, seq, kv, before in resident:
            if self.place.setdefault(sid, p) != p:
                self.migrated.add(sid)
                self.place[sid] = p
            if seq.generated > before:
                self.requests[sid].served.append(kv.token)
                per_place.setdefault(p, []).append(before + 1)
                self.token_keys.append(before + 1)
        mb = self.engine.max_batch
        for keys in per_place.values():
            for lo in range(0, len(keys), mb):
                self.calls.append(keys[lo:lo + mb])
        done = len(d.completed) - self.seen_done
        self.window_done.extend(d.completed[self.seen_done:])
        self.seen_done = len(d.completed)
        with jax.profiler.TraceAnnotation("bench.admit"):
            for _ in range(done):
                self._admit()
        self.rounds += 1
        return int(info["decoded"])

    def _migrate(self, k: int) -> None:
        """One window that migrates ``k`` requests, their sequence and KV
        together, through the program's relocation path as a balancer
        window does: round robin from the fullest replicas, each to the
        replica after it."""
        from repro.core import CollectiveMoveManager

        d = self.driver
        d.sync()
        members = list(d.group.members)
        held = {p: sorted(d.seqs.keys(p)) for p in members}
        assign: dict[int, dict[int, int]] = {}
        for i in range(k):
            src = max(members, key=lambda p: (len(held[p]), -p))
            if len(held[src]) <= 1:
                break
            dest = members[(members.index(src) + 1 + i) % len(members)]
            if dest == src:
                dest = members[(members.index(src) + 1) % len(members)]
            assign.setdefault(src, {})[held[src].pop()] = dest
        mm = CollectiveMoveManager(d.group, transport=d.transport)
        for src, mapping in assign.items():
            rule = (lambda key, m=mapping, s=src: m.get(key, s))
            d.seqs.move_at_sync(src, rule, mm)
            d.kv.move_at_sync(src, rule, mm)
        mm.sync_async(update_dists=(d.seqs, d.kv)).finish()
        d.router.refresh()

    # -- the harness's interface -------------------------------------------
    def warm(self) -> None:
        import jax

        # each batch bucket compiles once, untimed, on first use
        probe = jax.device_put(self.engine.new_seq(1))
        b = 1
        while b <= self.engine.max_batch:
            self.engine.decode_batch([probe] * b)
            b *= 2
        del probe
        for _ in range(self.traffic.population):
            self._admit()
        # the balancer's windows compile a program for each count of
        # requests they carry: warm the counts it sends most
        for k in self.mix["warm_migrations"]:
            self._migrate(int(k))
        for _ in range(int(self.mix["warmup_rounds"])):
            self._round()
        self.window_done.clear()
        self.calls.clear()
        self.token_keys.clear()

    def run_window(self, seconds: float) -> dict:
        import jax

        times, tokens = [], 0
        t_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.round"):
                    tokens += self._round()
            except Exception:  # noqa: BLE001 - a failed round is counted
                if not self.failed_rounds:
                    traceback.print_exc()
                self.failed_rounds += 1
                self.rounds += 1
            t1 = time.perf_counter()
            times.append(t1 - t0)
            if t1 - t_start >= seconds:
                break
        elapsed = t1 - t_start
        return {
            "metrics": {"tokens_per_s": tokens / elapsed,
                        "round_p95_ms": float(np.percentile(times, 95)) * 1e3},
            "attempted": len(self.requests),
            "failed": self.failed_admissions + self.failed_rounds,
            "counters": {"rounds": len(times), "tokens": tokens,
                         "completed": len(self.window_done),
                         "round_median_ms": float(np.median(times)) * 1e3,
                         "round_s": float(sum(times)),
                         "migrated_rows": self.driver.transport.lifetime.rows,
                         "calls": list(self.calls),
                         "token_keys": list(self.token_keys)},
        }

    def finish(self) -> dict:
        import jax

        d = self.driver
        d.sync()
        lost = d.lost()
        done = [sid for sid in self.window_done if sid in self.requests]
        # the longest finished request, one that migrated (when one did),
        # and the rest drawn from the seed
        picked = []
        rng = np.random.default_rng(self.seed)
        if done:
            picked.append(max(done, key=lambda s: (self.requests[s].max_new,
                                                   -s)))
            moved = sorted(s for s in done
                           if s in self.migrated and s not in picked)
            if moved:
                picked.append(moved[int(rng.integers(len(moved)))])
            rest = sorted(s for s in done if s not in picked)
            k = min(int(self.mix["check_requests"]) - len(picked), len(rest))
            picked += [rest[i] for i in sorted(rng.choice(len(rest), k,
                                                          False))]
        samples = []
        for sid in picked:
            r = self.requests[sid]
            served = [int(np.asarray(t).reshape(-1)[0])
                      for t in jax.device_get(r.served)]
            samples.append({"sid": sid, "start": r.start,
                            "max_new": r.max_new,
                            "first": int(np.asarray(r.first).reshape(-1)[0]),
                            "served": served})
        self.driver = self.engine = self.requests = None
        return {"samples": samples, "lost": lost, "finished": len(done),
                "failed": self.failed_rounds + self.failed_admissions}


def check(config: dict, mix: dict, seed: int, outcome: dict, *,
          control: bool = False) -> dict:
    """``{name: (value, limit)}`` for the program's run, or with
    ``control`` for the reference put in its place one precision down:
    at each served position, the gap of the token that the reference
    with float8 weights puts first."""
    import jax.numpy as jnp

    limit = config["correct_limits"]["max_logit_gap"]
    vocab = config["vocab_size"]
    params = weights.qwen2_params(config, seed, config["torch_dtype"])
    widest, count_errors, tokens = 0.0, 0, 0
    for s in outcome["samples"]:
        served = s["served"]
        count_errors += abs(len(served) - s["max_new"])
        if not served:
            continue
        tokens += len(served)
        inputs = [s["first"]] + served[:-1]
        lg = ref.logits(params, config, inputs, s["start"])
        rows = jnp.arange(lg.shape[0])
        best = jnp.max(lg, axis=-1)
        if control:
            low = ref.logits(params, config, inputs, s["start"],
                             weight_dtype="float8_e4m3fn")
            gaps = np.asarray(best - lg[rows, jnp.argmax(low, axis=-1)])
        else:
            y = np.zeros(lg.shape[0], np.int64)
            y[:len(served)] = served
            inside = y < vocab
            got = lg[rows, jnp.asarray(np.where(inside, y, 0))]
            gaps = np.where(inside, np.asarray(best - got), _FAR)
        widest = max(widest, float(gaps[:len(served)].max()))
    return {
        "max_logit_gap": (widest, limit),
        "served_count_errors": (count_errors, 0),
        "lost_requests": (outcome["lost"], 0),
        "failed_rounds": (outcome["failed"], 0),
        "unchecked": (0 if tokens else 1, 0),
    }
