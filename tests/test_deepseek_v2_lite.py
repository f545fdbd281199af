"""DeepSeek-V2-Lite at one chip's expert-parallel share, at a CPU size.

The configuration holds a share of each layer's routed experts and
routes over all of them; its latent attention takes YaRN rope and the
router leaves the top-k weights unnormalised.  These tests hold the
serving path (``DecodeEngine`` behind ``ElasticServingDriver``, KV
migrating over ``DeviceTransport``) to the benchmark's plain float32
reference, the held shares to the uncut layer, YaRN to its formulas,
and the routing counters to the rows they count.
"""
import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from repro.configs import get_config  # noqa: E402
from repro.core import GLBConfig, telemetry  # noqa: E402
from repro.models import Parallel, zoo  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import Yarn  # noqa: E402
from repro.models.layers import yarn_inv_freq, yarn_mscale  # noqa: E402
from repro.models.moe import (held_experts_forward, mla_softmax_scale,  # noqa: E402
                              moe_forward_dense, moe_init, route)
from repro.serving import DecodeEngine, serving_config  # noqa: E402

PAR = Parallel(mesh=None)
PUBLISHED = ROOT / "bench" / "configs" / "deepseek_v2_lite_ep8_elastic4.json"
# d 64, 3 layers (1 dense + 2 expert), 16 routed experts of which the
# first 4 are held, top-3, latent 32, rope 8
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, n_routed_experts=4, num_experts_per_tok=3,
            n_shared_experts=1, vocab_size=300, torch_dtype="float32")


def tiny_config(**overrides) -> dict:
    cfg = json.loads(PUBLISHED.read_text())
    cfg.update(TINY, deployment=dict(cfg["deployment"], routed_experts=16))
    cfg.update(overrides)
    return cfg


def program_config(cfg: dict):
    from bench.systems.elastic_serving_mla_moe import model_config
    return model_config(cfg)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


# -- (a) the serving path against the reference ------------------------------
def test_elastic_decode_with_a_migration_matches_the_reference():
    """Three requests decode on replica 0, one migrates to replica 1 over
    the device transport, all decode on.  In float32 the program and the
    reference agree to about 4e-6 in the logits here, so every served
    token is the reference's argmax up to a gap of 1e-4."""
    from bench.reference import deepseek_v2 as ref
    from bench.systems.elastic_serving import _weights_from_benchmark
    from bench.weights_mla_moe import deepseek_v2_params
    from repro.core import CollectiveMoveManager
    from repro.serving.elastic import ElasticServingDriver

    cfgd = tiny_config()
    params = deepseek_v2_params(cfgd, 11, "float32")
    with _weights_from_benchmark(params):
        engine = DecodeEngine(program_config(cfgd), s_cache=32, max_batch=4,
                              seed=11)
    d = ElasticServingDriver(2, slots_per_replica=4,
                             glb=GLBConfig(period=10 ** 9), engine=engine,
                             transport="device")
    starts = {}
    for start in (3, 9, 14):
        sid = d.admit(start, 20, place=0)
        starts[sid] = start
    kvs = {sid: d.kv.handle(0)[sid] for sid in starts}
    inputs = {sid: [int(np.asarray(kv.token)[0, 0])]
              for sid, kv in kvs.items()}

    def decode(rounds):
        for _ in range(rounds):
            d.decode_round()
            for sid in starts:
                kv = next(d.kv.handle(p)[sid] for p in d.group.members
                          if sid in d.kv.handle(p))
                inputs[sid].append(int(np.asarray(kv.token)[0, 0]))

    decode(4)
    moved = max(starts)
    d.sync()
    mm = CollectiveMoveManager(d.group, transport=d.transport)
    rule = (lambda key: 1 if key == moved else 0)
    d.seqs.move_at_sync(0, rule, mm)
    d.kv.move_at_sync(0, rule, mm)
    mm.sync_async(update_dists=(d.seqs, d.kv)).finish()
    d.router.refresh()
    assert moved in d.kv.handle(1) and moved not in d.kv.handle(0)
    assert d.transport.lifetime.rows > 0
    decode(4)

    for sid, toks in inputs.items():
        served = toks[1:]
        lg = np.asarray(ref.logits(params, cfgd, toks[:-1], starts[sid]))
        lg = lg[:len(served)]
        gap = lg.max(axis=1) - lg[np.arange(len(served)), served]
        assert gap.max() <= 1e-4, (sid, gap)


# -- (b) the held shares add up to the uncut layer ---------------------------
def test_four_disjoint_shares_add_up_to_the_uncut_layer():
    full = dataclasses.replace(program_config(tiny_config()),
                               held_experts=None, capacity_factor=8.0)
    p = moe_init(jax.random.PRNGKey(3), full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, full.d_model))
    uncut, _ = moe_forward_dense(p, full, x[None])
    total = 0.0
    for first in range(0, 16, 4):
        share = dataclasses.replace(full, held_experts=range(first,
                                                             first + 4))
        bank = jax.tree_util.tree_map(lambda a: a[first:first + 4],
                                      p["experts"])
        part, _, _ = held_experts_forward(p["router"], bank, share, x, first)
        total = total + part
    total = total + (jax.nn.silu(x @ p["shared"]["wg"]["w"])
                     * (x @ p["shared"]["wi"]["w"])) @ p["shared"]["wo"]["w"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut[0]),
                               rtol=0, atol=2e-5)


# -- (c) YaRN against its formulas -------------------------------------------
V2 = get_config("deepseek_v2_lite_16b")


def _corr(rotations, dim=64, base=10000.0, orig=4096):
    return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))


def test_yarn_correction_range_at_the_published_numbers():
    y = V2.yarn
    assert y == Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
    assert math.floor(_corr(y.beta_fast)) == 10
    assert math.ceil(_corr(y.beta_slow)) == 23


def test_yarn_frequencies_follow_the_ramp():
    inv = np.asarray(yarn_inv_freq(64, 10000.0, V2.yarn))
    i = np.arange(32)
    extra = 10000.0 ** (-2.0 * i / 64)
    mask = 1.0 - np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)  # kept
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)


def test_yarn_attention_scale():
    m = yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    assert mla_softmax_scale(V2) == pytest.approx(192 ** -0.5 * m * m)
    # without YaRN the scale is the plain one
    assert mla_softmax_scale(get_config("deepseek_v3_671b")) == \
        pytest.approx(192 ** -0.5)


# -- (d) unnormalised routing; V3 keeps its own ------------------------------
def _router(cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (cfg.d_model, cfg.n_experts)) / 4
    x = jax.random.normal(jax.random.fold_in(key, 1), (6, cfg.d_model))
    probs = jax.nn.softmax(x @ w, axis=-1)
    return {"w": {"w": w}}, x, np.asarray(probs)


def test_v2_lite_leaves_top_k_weights_unnormalised():
    assert V2.norm_topk_prob is False
    cfg = V2.reduced(n_experts=16, top_k=6)
    r, x, probs = _router(cfg)
    w, idx, _ = route(r, x, 6, n_experts=16, normalize=cfg.norm_topk_prob)
    w, idx = np.asarray(w), np.asarray(idx)
    np.testing.assert_allclose(w, np.take_along_axis(probs, idx, 1),
                               rtol=1e-6)
    assert (w.sum(axis=1) < 1 - 1e-3).all()


def test_v3_routing_is_unchanged():
    v3 = get_config("deepseek_v3_671b").reduced(n_experts=16, top_k=4)
    assert v3.norm_topk_prob is True
    r, x, probs = _router(v3, seed=1)
    w, idx, _ = route(r, x, 4, n_experts=16, normalize=v3.norm_topk_prob)
    top = -np.sort(-probs, axis=1)[:, :4]
    np.testing.assert_allclose(np.asarray(w),
                               top / top.sum(axis=1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.argsort(-probs, axis=1)[:, :4])


# -- (e) no assignment to a held expert is dropped ---------------------------
@pytest.mark.parametrize("tokens", [1, 4, 8])
def test_no_held_assignment_is_dropped_at_decode_batches(tokens):
    """Every token routes to the same three held experts (the router
    favours them by far): each receives all ``tokens`` rows, which the
    capacity keeps, so the part equals a token-by-token sum."""
    cfg = dataclasses.replace(program_config(tiny_config()),
                              held_experts=range(4, 8))
    p = moe_init(jax.random.PRNGKey(5), cfg, jnp.float32)
    bias = np.zeros((cfg.d_model, 16), np.float32)
    bias[:, 4:7] = 3.0
    router = {"w": {"w": p["router"]["w"]["w"] + bias}}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6),
                                  (tokens, cfg.d_model)))
    part, _, idx = held_experts_forward(router, p["experts"], cfg, x, 4)
    idx = np.asarray(idx)
    assert (np.sort(idx, axis=1) == [4, 5, 6]).all()
    w, _, _ = route(router, x, 3, n_experts=16, normalize=False)
    e = p["experts"]
    want = np.zeros((tokens, cfg.d_model), np.float32)
    for t in range(tokens):
        for k in range(3):
            j = idx[t, k] - 4
            h = jax.nn.silu(x[t] @ e["wg"][j]) * (x[t] @ e["wi"][j])
            want[t] += float(w[t, k]) * np.asarray(h @ e["wo"][j])
    np.testing.assert_allclose(np.asarray(part), want, rtol=1e-5, atol=1e-5)


# -- (f) the routing counters --------------------------------------------------
def test_moe_counters_count_real_rows_only():
    from bench.systems.elastic_serving import _weights_from_benchmark
    from bench.weights_mla_moe import deepseek_v2_params
    from repro.serving.decode import _stack_states

    cfgd = tiny_config()
    cfg = program_config(cfgd)
    with _weights_from_benchmark(deepseek_v2_params(cfgd, 2, "float32")):
        engine = DecodeEngine(cfg, s_cache=16, max_batch=4, seed=2)
    seqs = [jax.device_put(engine.new_seq(3 + i)) for i in range(3)]
    moe_layers, k = cfg.n_layers - cfg.first_dense_layers, cfg.top_k

    # a batch whose padding row repeats row 0: counting 4 rows adds row
    # 0's assignments again but hits no new expert; counting 3 leaves
    # the padding out
    batch = _stack_states([s.state for s in seqs] + [seqs[0].state])
    toks = jnp.concatenate([s.token for s in seqs] + [seqs[0].token])
    step = jax.jit(lambda s, t, n: T.decode_step(engine.params, cfg, PAR, s,
                                                 t, count_rows=n)[2])
    c1, c3, c4 = (np.asarray(step(batch, toks, jnp.int32(n)))
                  for n in (1, 3, 4))
    assert c3[0] == 3 * k * moe_layers and c4[0] == 4 * k * moe_layers
    assert c4[1] == c3[1] + c1[1] and c4[2] == c3[2]
    assert 0 < c3[1] < c3[0] and 0 < c3[2] <= min(c3[1], moe_layers * 4)
    # the counted step keeps the program name the decode-step rooflines
    # read in the trace
    lowered = engine._step.lower(engine.params, batch, toks,
                                 engine._moe_counts, engine._rows[4])
    assert lowered.as_text().startswith("module @jit_serve_step ")

    telemetry.enable()
    engine.decode_batch(seqs)          # bucket 4, one padding row; the
    engine.decode_batch(seqs[:1])      # warm-up steps are not counted
    counts = engine.moe_counts()
    assert counts["moe.routed_assignments"] == (3 + 1) * k * moe_layers
    assert counts["moe.held_assignments"] == c3[1] + c1[1]
    assert telemetry.metrics_dict()["moe.held_assignments"] == \
        counts["moe.held_assignments"]


def test_moe_counters_are_absent_without_experts():
    engine = DecodeEngine(serving_config(n_layers=1, d_model=32, d_ff=64,
                                         vocab_size=64),
                          s_cache=8, max_batch=2)
    seqs = [jax.device_put(engine.new_seq(2)) for _ in range(2)]
    engine.decode_batch(seqs)
    assert engine.moe_counts() is None
    batch = jax.eval_shape(lambda: T.init_decode_state(engine.cfg, 2, 8))
    out = jax.eval_shape(engine._step, engine.params, batch,
                         jax.ShapeDtypeStruct((2, 1), jnp.int32))
    assert len(out) == 2                 # state and tokens, nothing else


# -- the held share in the parameter count -----------------------------------
def test_param_counts_count_the_held_experts():
    cfg = program_config(tiny_config())
    params = zoo.init_params(cfg, 0)
    actual = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert abs(cfg.param_counts()["total"] - actual) < 0.25 * actual
    whole = dataclasses.replace(cfg, held_experts=None)
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    assert whole.param_counts()["total"] - cfg.param_counts()["total"] == \
        moe_layers * 12 * 3 * cfg.d_model * cfg.d_ff_expert
