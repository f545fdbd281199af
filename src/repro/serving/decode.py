"""Real-decode data plane: the jitted model behind the elastic driver.

The ROADMAP's "Serving with real decode" item: instead of
:class:`~repro.serving.elastic.ServingSim`'s modeled decode times, a
:class:`DecodeEngine` runs ``models.transformer.decode_step`` (jitted,
bucketized batch shapes) over each replica's resident sequences and
reports *measured wall-clock* step times — the numbers that feed
:class:`~repro.serving.workload.TrafficWorkload`'s decode-EWMA and the
GLB's cost exchange, so rebalancing reacts to what the hardware actually
did (DASH-style measured, not modeled, adaptivity).

KV residency: every sequence's cache rows live in a :class:`SeqKV` — a
batch-1 slice of the model's decode-state pytree held as *device
buffers* inside the ``kv`` ``DistIdMap`` (bridged at admission through
``DistMap.to_device``).  Each round the engine stacks the resident
slices into one batch state, runs the jitted step, and writes the
updated slices back into the same ``SeqKV`` objects — mutation in place,
so a slice extracted into an in-flight migration window still lands with
its freshest pages.  A GLB window therefore moves sequence metadata and
device KV shards together through one ``sync_async``.

:class:`RealDecodeSim` is the §6.3-style harness on top: a skewed
cluster (``work[p]`` extra decode passes emulate a slow chip — the model
really runs ``work`` times, wall-clock measured), Poisson arrivals, and
lockstep rounds whose duration is the slowest live replica's measured
time.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry
from ..models import Parallel, zoo
from ..models import transformer as T
from .cache import SeqKV

__all__ = ["DecodeEngine", "RealDecodeSim", "serving_config"]


def serving_config(*, n_layers: int = 2, d_model: int = 128,
                   d_ff: int = 512, vocab_size: int = 1024):
    """The reduced decoder-only config the serving examples/benchmarks
    run (same family as ``examples/serve.py``)."""
    from ..configs import get_config
    return get_config("qwen2_1_5b").reduced(
        n_layers=n_layers, d_model=d_model, d_ff=d_ff,
        vocab_size=vocab_size)


# ---------------------------------------------------------------------------
# per-sequence state slicing (batch axis differs per state section)
# ---------------------------------------------------------------------------
def _stack_states(states: list) -> dict:
    """Batch-1 decode-state slices → one batch-B state.  ``pos`` /
    ``prefix`` / ``suffix`` leaves carry batch on axis 0; scanned-period
    leaves carry it on axis 1 (axis 0 is the layer period)."""
    cat0 = lambda *xs: jnp.concatenate(xs, axis=0)
    cat1 = lambda *xs: jnp.concatenate(xs, axis=1)
    return {
        "pos": cat0(*[s["pos"] for s in states]),
        "prefix": jax.tree_util.tree_map(cat0, *[s["prefix"] for s in states]),
        "suffix": jax.tree_util.tree_map(cat0, *[s["suffix"] for s in states]),
        "scan": jax.tree_util.tree_map(cat1, *[s["scan"] for s in states]),
    }


def _unstack_state(state: dict, n: int) -> list:
    """Inverse of :func:`_stack_states`: the first ``n`` batch slices.
    A leaf may hold several rows per sequence on its batch axis (the
    mLSTM state merges batch and heads, batch-major), so each sequence
    takes its own share of the rows."""
    batch = state["pos"].shape[0]

    def rows(i, axis):
        def take(a):
            k = a.shape[axis] // batch
            return jax.lax.slice_in_dim(a, i * k, (i + 1) * k, axis=axis)
        return take

    out = []
    for i in range(n):
        out.append({
            "pos": state["pos"][i:i + 1],
            "prefix": jax.tree_util.tree_map(rows(i, 0), state["prefix"]),
            "suffix": jax.tree_util.tree_map(rows(i, 0), state["suffix"]),
            "scan": jax.tree_util.tree_map(rows(i, 1), state["scan"]),
        })
    return out


def serve_stack(states: list, tokens: list):
    """The stack program: a micro-batch's batch-1 states and ``(1, 1)``
    tokens, padded to its bucket, as one batch state and ``(B, 1)``
    tokens."""
    return _stack_states(states), jnp.concatenate(tokens, axis=0)


def serve_unstack(state: dict, tokens):
    """The unstack program: every batch slice of a step's output state
    and tokens, padding slots included, so one program serves a bucket
    whatever the micro-batch's length (static slices)."""
    n = tokens.shape[0]
    return _unstack_state(state, n), [tokens[i:i + 1] for i in range(n)]


class DecodeEngine:
    """Jitted lockstep decode over per-sequence device KV slices.

    One engine (model + params + jit cache) is shared by every replica —
    a replica's step is ``decode_batch`` over *its* resident ``SeqKV``
    list.  A replica decodes in micro-batches of at most ``max_batch``
    sequences (the hardware slot limit of a real decoder): overflow runs
    as additional sequential steps, so a replica's measured time grows
    with its residency — the signal the traffic-keyed GLB balances on.
    Micro-batch shapes are padded to power-of-two buckets so the jit
    cache stays small (≤ log2(max_batch)+1 entries); each bucket is
    warmed untimed on first use so compilation never pollutes a measured
    decode time.  Stacking the slices into a batch and writing the
    step's output back are one compiled program each per bucket, so a
    micro-batch costs three dispatches, not one per leaf and sequence.
    """

    def __init__(self, cfg=None, *, s_cache: int = 128, max_batch: int = 8,
                 seed: int = 0):
        self.cfg = cfg if cfg is not None else serving_config()
        if self.cfg.is_encoder_decoder:
            raise ValueError("DecodeEngine serves decoder-only configs")
        self.par = Parallel(mesh=None)
        self.params = zoo.init_params(self.cfg, seed)
        self.s_cache = s_cache
        self.max_batch = int(max_batch)
        self.rng = np.random.default_rng(seed)

        # an MoE step also carries the routing counts of its real rows
        # (bucket padding excluded) in and out: no dispatch of its own.
        # Both steps are named serve_step: the trace's `jit_serve_step`
        # is what the decode-step roofline reads.
        self._moe_counts = None
        if self.cfg.is_moe:
            def serve_step(params, state, tokens, counts, rows):
                state, logits, routed = T.decode_step(
                    params, self.cfg, self.par, state, tokens,
                    count_rows=rows)
                return (state,
                        jnp.argmax(logits, -1)[:, None].astype(jnp.int32),
                        counts + routed)

            self._moe_counts = jnp.zeros((3,), jnp.int32)
            self._rows = [jnp.int32(k) for k in range(self.max_batch + 1)]
        else:
            def serve_step(params, state, tokens):
                state, logits = T.decode_step(params, self.cfg, self.par,
                                              state, tokens)
                return (state,
                        jnp.argmax(logits, -1)[:, None].astype(jnp.int32))
        self._step = jax.jit(serve_step)
        self._stack = jax.jit(serve_stack)
        self._unstack = jax.jit(serve_unstack)
        # host-side batch-1 template: admission builds SeqKVs from this
        # and the driver bridges them to device via ``kv.to_device``
        self._template = jax.tree_util.tree_map(
            np.asarray, T.init_decode_state(self.cfg, 1, s_cache))
        self._pad_state = jax.device_put(
            jax.tree_util.tree_map(np.copy, self._template))
        self._pad_token = jnp.zeros((1, 1), jnp.int32)
        self._warm: set[int] = set()
        self.steps = 0
        self.tokens_decoded = 0

    # -- admission ---------------------------------------------------------
    def new_seq(self, prompt_len: int) -> SeqKV:
        """Fresh host-side :class:`SeqKV`: empty cache, position advanced
        past the prompt, a random start token.  Host numpy on purpose —
        ``DistMap.to_device`` is the bridge that makes it a device shard.
        """
        state = jax.tree_util.tree_map(np.copy, self._template)
        state["pos"] = np.full((1,), int(prompt_len), np.int32)
        token = np.asarray(
            self.rng.integers(0, self.cfg.vocab_size, (1, 1)), np.int32)
        return SeqKV(state, token)

    def _bucket(self, n: int) -> int:
        return 1 << max(n - 1, 0).bit_length()

    # -- the measured lockstep step ---------------------------------------
    def _run(self, state, tokens, n: int, *, count: bool = False):
        """One step over a stacked micro-batch of ``n`` real rows: (state,
        tokens).  An MoE step adds the rows' routing to the counts when
        ``count``; a warm-up step leaves them as they were."""
        if self._moe_counts is None:
            return self._step(self.params, state, tokens)
        state, tokens, counts = self._step(self.params, state, tokens,
                                           self._moe_counts, self._rows[n])
        if count:
            self._moe_counts = counts
        return state, tokens

    def moe_counts(self) -> dict | None:
        """The routing counts of every counted step so far (None for a
        config without MoE), read with one ``device_get`` and published
        as telemetry counters: ``moe.routed_assignments`` (real tokens x
        top-k, over MoE layers), ``moe.held_assignments`` (those to the
        experts held here) and ``moe.held_experts_hit`` (held experts
        with at least one token, per step run and layer)."""
        if self._moe_counts is None:
            return None
        routed, held, hit = (int(v) for v in
                             jax.device_get(self._moe_counts))
        counts = {"moe.routed_assignments": routed,
                  "moe.held_assignments": held,
                  "moe.held_experts_hit": hit}
        if telemetry.enabled():
            for name, value in counts.items():
                telemetry.metrics().counter(name).set(value)
        return counts

    def decode_batch(self, seq_kvs: list, *, work: int = 1) -> float:
        """One decode step for every sequence in ``seq_kvs`` (mutated in
        place with updated state/token); returns the *measured* seconds
        the jitted model spent.  Sequences beyond ``max_batch`` decode
        as additional sequential micro-batch steps — a replica over its
        slot limit pays for it in wall clock, exactly what the balancer
        should see.  ``work`` repeats each step that many times
        (slow-chip emulation: the compute really runs) while the
        sequences still advance a single token."""
        n = len(seq_kvs)
        if n == 0:
            return 0.0
        prepared = []   # (chunk, stacked state, tokens) — built untimed
        with telemetry.span("serve.stack", seqs=n):
            for lo in range(0, n, self.max_batch):
                chunk = seq_kvs[lo:lo + self.max_batch]
                bucket = self._bucket(len(chunk))
                pad = bucket - len(chunk)
                state, tokens = self._stack(
                    [kv.state for kv in chunk] + [self._pad_state] * pad,
                    [kv.token for kv in chunk] + [self._pad_token] * pad)
                if bucket not in self._warm:   # compile untimed, uncounted
                    jax.block_until_ready(self._unstack(
                        *self._run(state, tokens, len(chunk))))
                    self._warm.add(bucket)
                    telemetry.inc("serve.batch_programs_built")
                prepared.append((chunk, state, tokens))
        # drain the async dispatch queue (stacking above, unstacking from
        # earlier calls) so the timed window measures *this* decode only
        jax.block_until_ready([s for _, s, _ in prepared])
        with telemetry.span("serve.decode_batch", seqs=n, work=work):
            t0 = time.perf_counter()
            outs = []
            for chunk, state, tokens in prepared:
                for _ in range(max(int(work), 1)):
                    out = self._run(state, tokens, len(chunk), count=True)
                outs.append(out)
            jax.block_until_ready(outs)
            dt = time.perf_counter() - t0
        chunks = [chunk for chunk, _, _ in prepared]
        # drop the stacked inputs before their slices are made, so the
        # peak holds one batch of KV less
        del prepared, state, tokens
        if telemetry.enabled():
            telemetry.observe("serve.decode_s", dt)
        with telemetry.span("serve.unstack", seqs=n):
            for chunk, out in zip(chunks, outs):
                states, tokens = self._unstack(*out)
                for kv, new_state, token in zip(chunk, states, tokens):
                    kv.state = new_state
                    kv.token = token
        self.steps += 1
        self.tokens_decoded += n
        return dt


# ---------------------------------------------------------------------------
# skewed-cluster harness on the real data plane
# ---------------------------------------------------------------------------
@dataclass
class RealDecodeSim:
    """Lockstep serving rounds against :class:`DecodeEngine`.

    Replica ``p`` runs ``work[p]`` jitted decode passes per round (an
    honestly-slow chip); the round's simulated duration is the slowest
    live replica's *measured* time.  ``work_from`` delays the skew — the
    §6.3 "disturbed cluster" shape: sequences place evenly while the
    cluster is even, then a chip degrades mid-run and only *relocation*
    can move the residents off it (admission only steers new arrivals).
    Pass a shared ``engine`` so balanced/unbalanced comparisons reuse
    one jit cache.
    """

    n_replicas: int = 4
    slots: int = 16
    work: tuple = ()                 # per-replica decode passes per round
    work_from: int = 0               # round at which the skew activates
    preload: tuple = ()              # (replica, count): hot-shard residency
    preload_max_new: tuple = (48, 64)
    arrival_rate: float = 3.0
    prompt_range: tuple = (8, 48)
    max_new_range: tuple = (8, 24)
    fail_at: dict = field(default_factory=dict)
    glb_period: int = 4
    policy: str = "proportional"
    balance: bool = True
    heartbeat_timeout: int = 2
    pipeline_depth: int = 1      # 2 = double-buffered migration windows:
    #                              window N's KV delivery overlaps the
    #                              decode rounds while window N+1 packs
    transport: object = None     # relocation data plane ("host"/"device":
    #                              KV migration windows ship device pages
    #                              through the jitted all_to_all)
    seed: int = 0
    engine: DecodeEngine | None = None

    def __post_init__(self):
        from ..core import GLBConfig
        from .elastic import ElasticServingDriver
        if self.engine is None:
            self.engine = DecodeEngine()
        period = self.glb_period if self.balance else 10 ** 9
        self.driver = ElasticServingDriver(
            self.n_replicas, slots_per_replica=self.slots,
            glb=GLBConfig(period=period, policy=self.policy, ema=0.3,
                          asynchronous=True,
                          pipeline_depth=self.pipeline_depth),
            heartbeat_timeout=self.heartbeat_timeout,
            engine=self.engine, transport=self.transport)
        if not self.work:
            self.work = (1,) * self.n_replicas
        self.rng = np.random.default_rng(self.seed)
        if self.preload:
            # skewed residency (a hot tenant / sticky-session pathology):
            # long-lived sequences pinned to one replica — admission only
            # steers *new* arrivals, so spreading these is relocation's job
            replica, count = self.preload
            for _ in range(count):
                self.driver.admit(int(self.rng.integers(*self.prompt_range)),
                                  int(self.rng.integers(
                                      *self.preload_max_new)),
                                  place=replica)
        self.failed: set[int] = set()
        self.round_times: list[float] = []   # slowest live replica, measured
        self.round_tokens: list[int] = []
        self.tokens = 0
        self.iter = 0

    def run(self, rounds: int) -> "RealDecodeSim":
        d = self.driver
        for _ in range(rounds):
            if self.iter in self.fail_at:
                self.failed.add(self.fail_at[self.iter])
            for _ in range(self.rng.poisson(self.arrival_rate)):
                d.admit(int(self.rng.integers(*self.prompt_range)),
                        int(self.rng.integers(*self.max_new_range)))
            w = self.work if self.iter >= self.work_from else None
            info = d.decode_round(failed=self.failed, work=w)
            t = info["decode_s"]
            finite = t[np.isfinite(t)]
            self.round_times.append(float(finite.max()) if len(finite) else 0.0)
            self.round_tokens.append(info["decoded"])
            self.tokens += info["decoded"]
            self.iter += 1
        d.sync()
        return self

    def throughput(self, *, trim: float = 0.1, skip: int = 0,
                   until: int | None = None) -> float:
        """Tokens per second of simulated-concurrent serving: replicas
        decode in parallel, so a round costs its slowest measured time.

        Wall-clock maxima are noise amplifiers — one scheduler hiccup on
        any replica sets that round's time — so the ``trim`` fraction of
        slowest rounds is dropped *with their tokens* before dividing
        (a trimmed estimator, not a thumb on the scale: both sides of a
        comparison shed their outliers the same way).  ``skip``/``until``
        bound the measured window — e.g. the recovery transient after a
        disturbance: before it the runs are identical, and long after it
        retirement drains the skew even without relocation, so both
        tails only dilute the comparison."""
        times = np.asarray(self.round_times[skip:until])
        toks = np.asarray(self.round_tokens[skip:until], np.float64)
        if len(times) == 0:
            return 0.0
        keep = len(times) - int(trim * len(times))
        order = np.argsort(times)[:max(keep, 1)]
        wall = float(times[order].sum())
        return float(toks[order].sum()) / wall if wall > 0 else 0.0

    def window_p95(self) -> list[float]:
        from .elastic import window_p95
        return window_p95(self.round_times, self.glb_period)
