"""The serving engine's stack and unstack programs.

``DecodeEngine.decode_batch`` stacks a micro-batch's batch-1 decode
states into one batch state and writes the step's output back through
one compiled program each per batch bucket.  These tests hold the
compiled programs to ``_stack_states`` / ``_unstack_state`` called as
plain functions, bit for bit, over the decode-state layouts the engine
serves, and check that a warmed engine lowers no program afterwards,
also for KV that arrived through the device transport.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (CollectiveMoveManager, DeviceTransport, DistIdMap,
                        PlaceGroup, telemetry)
from repro.serving import DecodeEngine, serving_config
from repro.serving.decode import _stack_states, _unstack_state

COMPILE_SPANS = ("jax.trace", "jax.lower", "jax.compile")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _assert_same_tree(got, want):
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        assert a.tobytes() == b.tobytes()


def _random_state(template, rng):
    def leaf(a):
        if np.issubdtype(a.dtype, np.integer):
            return rng.integers(0, 1000, a.shape).astype(a.dtype)
        return rng.standard_normal(a.shape).astype(a.dtype)
    return jax.device_put(jax.tree_util.tree_map(leaf, template))


# a scanned stack of attention periods; MLA with a dense prefix layer;
# recurrent states beside local attention; mLSTM and sLSTM states
LAYOUTS = ("qwen2_1_5b", "deepseek_v2_lite_16b", "recurrentgemma_2b",
           "xlstm_350m")


@pytest.fixture(scope="module")
def engines():
    made = {}

    def get(arch):
        if arch not in made:
            made[arch] = DecodeEngine(get_config(arch).reduced(),
                                      s_cache=8, max_batch=4)
        return made[arch]
    return get


@pytest.mark.parametrize("n", [1, 3, 4])
@pytest.mark.parametrize("arch", LAYOUTS)
def test_compiled_stack_and_unstack_match_plain_functions(engines, arch, n):
    engine = engines(arch)
    rng = np.random.default_rng(n)
    states = [_random_state(engine._template, rng) for _ in range(n)]
    tokens = [jax.device_put(rng.integers(0, 100, (1, 1)).astype(np.int32))
              for _ in range(n)]
    bucket = engine._bucket(n)
    pad = bucket - n
    ins = states + [engine._pad_state] * pad
    toks = tokens + [engine._pad_token] * pad

    state, token_batch = engine._stack(ins, toks)
    _assert_same_tree(state, _stack_states(ins))
    _assert_same_tree(token_batch, jnp.concatenate(toks, axis=0))

    out_states, out_tokens = engine._unstack(state, token_batch)
    assert len(out_states) == len(out_tokens) == bucket
    _assert_same_tree(out_states, _unstack_state(state, bucket))
    # the round trip gives back each sequence's own slice, padding apart
    _assert_same_tree(out_states[:n], states)
    _assert_same_tree(out_tokens[:n], tokens)


def _compile_spans():
    return [r for r in telemetry.tracer().records()
            if r["name"] in COMPILE_SPANS]


def _built():
    return telemetry.metrics_dict().get("serve.batch_programs_built", 0)


def test_warm_engine_lowers_nothing_also_for_migrated_kv():
    max_batch = 4
    engine = DecodeEngine(serving_config(n_layers=1, d_model=32, d_ff=64,
                                         vocab_size=64),
                          s_cache=16, max_batch=max_batch)
    g = PlaceGroup(2)
    kv = DistIdMap(g)
    for p in g.members:
        kv.handle(p)
    for k in range(2 * max_batch):
        kv.put(0, k, engine.new_seq(3 + k))
    kv.to_device(0)
    resident = [kv.handle(0)[k] for k in range(2 * max_batch)]

    telemetry.enable()
    b = 1
    while b <= max_batch:       # warm each bucket, as serving set-up does
        engine.decode_batch(resident[:b])
        b *= 2
    assert _built() == 3 and _compile_spans()   # buckets 1, 2 and 4
    # ship half the sequences to place 1 through the device transport
    mm = CollectiveMoveManager(g, transport=DeviceTransport())
    kv.move_at_sync(0, lambda k: 1 if k < max_batch else 0, mm)
    mm.sync_async(update_dists=(kv,)).finish()
    migrated = [kv.handle(1)[k] for k in sorted(kv.keys(1))]
    staying = [kv.handle(0)[k] for k in sorted(kv.keys(0))]
    assert len(migrated) == max_batch
    assert all(m.on_device() for m in migrated)

    telemetry.reset()
    for n in range(1, max_batch + 1):
        engine.decode_batch(staying[:n])
        engine.decode_batch(migrated[:n])
        engine.decode_batch(migrated[:n] + staying[:n])   # 2 to 8 sequences
    assert _compile_spans() == []
    assert _built() == 0
