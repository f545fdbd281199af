"""The plain references against the program at tiny sizes on the CPU,
and one deliberately wrong answer each that the comparison refuses."""
import numpy as np
import pytest

from bench import generators, weights
from bench.reference import qwen2, relocation as rl
from bench.tests.conftest import TINY_MODEL

# -- relocation -------------------------------------------------------------
CONFIG = {"places": 4, "rows_per_place": 64}
MIX = {"kind": "ycsb_zipf_blocks", "block_records": 8, "zipf_theta": 0.99,
       "zipf_items": 10_000_000_000, "zipf_zetan": 26.46902820178302,
       "head_bits": 10, "drift_blocks": 1, "moves_per_window": 3,
       "warmup_windows": 2}


def _traffic(seed=0):
    return generators.load(MIX, CONFIG, seed)


def test_fnvhash64_by_hand():
    from bench.generators.ycsb_zipf_blocks import fnvhash64

    def by_hand(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * 1099511628211) % (1 << 64)
            v >>= 8
        return abs(h - (1 << 64) if h >= 1 << 63 else h)

    vals = [0, 1, 255, 256, 123456789, (1 << 40) + 7]
    assert fnvhash64(np.array(vals)).tolist() == [by_hand(v) for v in vals]


def test_popularity_is_ycsb_zipfian():
    t = _traffic()
    assert t.popularity.shape == (32,)
    assert t.popularity.sum() == pytest.approx(1.0)
    # the hottest rank alone draws 1 / zetan of the requests
    assert t.popularity.max() >= 1 / 26.46902820178302


def test_plan_spends_the_budget_and_evens_the_load():
    t = _traffic()
    warm = list(t.warmup())
    # one window for each pair size 1, 2, 3 blocks, then the mix's two
    assert len(warm) == 3 + 2
    assert [sum(1 for m in w if m[2:] == warm[0][0][2:]) for w in warm[:3]] \
        == [1, 2, 3]
    for w in warm + [t.next_window() for _ in range(20)]:
        assert len(w) == 3
        assert all(e - s == 8 and src != dest for s, e, src, dest in w)
    load = np.roll(t.popularity, (t.window - 1 + t.phase) % 32)
    place = np.bincount(t.owner, weights=load, minlength=4)
    assert place.max() / place.mean() < 1.5


def test_seed_turns_the_phase_not_the_work():
    a, b = _traffic(0), _traffic(5)
    assert (a.popularity == b.popularity).all()
    assert a.phase == 0 and b.phase == 5


def test_model_owner_replays_the_plan():
    owner, moved = rl.model_owner(_traffic(3), 6)
    t = _traffic(3)
    plan = list(t.warmup()) + [t.next_window()]
    assert moved == sum(e - s for w in plan for s, e, _, _ in w)
    assert (owner == np.repeat(t.owner, 8)).all()
    assert np.bincount(owner, minlength=4).sum() == 256


@pytest.fixture
def case():
    data = np.random.default_rng(1).standard_normal((20, 6)).astype(np.float32)
    owner = np.repeat(np.arange(4), 5)
    owner[[0, 1, 2]] = 1           # three rows moved from place 0 to 1
    return data, owner


def test_compare_accepts_the_model(case):
    data, owner = case
    checks = rl.compare(rl.model_holdings(owner, data),
                        rl.owner_ranges(owner), data, owner, 3, 3)
    assert all(v == 0 for v, _ in checks.values())


def test_compare_refuses_a_corrupted_row(case):
    data, owner = case
    held = rl.model_holdings(owner, data)
    rows, idx = held[1]
    rows = rows.copy()
    rows.view(np.uint32)[2, 4] ^= 1
    held[1] = (rows, idx)
    checks = rl.compare(held, rl.owner_ranges(owner), data, owner, 3, 3)
    assert checks["rows_corrupt"][0] == 1


def test_compare_refuses_lost_duplicated_and_misplaced(case):
    data, owner = case
    held = rl.model_holdings(owner, data)
    rows, idx = held[2]
    held[2] = (rows[1:], idx[1:])                       # one lost
    rows, idx = held[3]
    held[3] = (np.concatenate([rows, data[:1]]), np.append(idx, 0))
    owners = rl.owner_ranges(owner)
    owners.append((19, 20, 0))                          # wrong owner
    checks = rl.compare(held, owners, data, owner, 3, 4)
    assert checks["lost_or_duplicated"][0] == 2
    assert checks["misplaced"][0] == 1                  # the copy of 0
    assert checks["dist_errors"][0] == 1
    assert checks["wire_rows_error"][0] == 1


def test_bf16_control_fails(case):
    data, owner = case
    held = rl.model_holdings(owner, rl.bf16_rows(data))
    checks = rl.compare(held, rl.owner_ranges(owner), data, owner, 3, 3)
    assert checks["rows_corrupt"][0] == 20


# -- Qwen2 ------------------------------------------------------------------
CFG = dict(TINY_MODEL, rms_norm_eps=1e-6, rope_theta=1e6,
           tie_word_embeddings=True, torch_dtype="float32",
           program_config="qwen2_1_5b")


def _program_logits(cfg_dict, params, tokens, start):
    """The program's own decode step, token after token, from an empty
    cache at position ``start``: the serving path's semantics."""
    import jax
    import jax.numpy as jnp

    from bench.systems.elastic_serving import model_config
    from repro.models import Parallel
    from repro.models import transformer as T

    cfg = model_config(cfg_dict)
    par = Parallel(mesh=None)
    state = T.init_decode_state(cfg, 1, 64)
    state["pos"] = jnp.full((1,), start, jnp.int32)
    step = jax.jit(lambda p, s, t: T.decode_step(p, cfg, par, s, t))
    out = []
    for t in tokens:
        state, lg = step(params, state, jnp.full((1, 1), t, jnp.int32))
        out.append(np.asarray(lg[0, :cfg_dict["vocab_size"]]))
    return np.stack(out)


@pytest.mark.parametrize("start", [0, 37])
def test_reference_matches_the_program_in_float32(start):
    params = weights.qwen2_params(CFG, 5, "float32")
    tokens = [3, 17, 299, 42, 0, 8]
    want = _program_logits(CFG, params, tokens, start)
    got = np.asarray(qwen2.logits(params, CFG, tokens, start))
    assert got.shape == (64, want.shape[1])
    got = got[:len(tokens)]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    # the same logits rounded to bfloat16 are outside that tolerance
    import jax.numpy as jnp

    rounded = np.asarray(jnp.asarray(got).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    assert np.abs(rounded - want).max() > 2e-4


def test_serving_check_refuses_an_altered_token():
    from bench.systems import elastic_serving as es

    cfg = dict(CFG, torch_dtype="bfloat16",
               correct_limits={"max_logit_gap": 0.01})
    params = weights.qwen2_params(cfg, 9, "bfloat16")
    first, start, served = 5, 11, []
    inputs = [first]
    for _ in range(8):          # greedy tokens of the reference itself
        lg = np.asarray(qwen2.logits(params, cfg, inputs, start))
        served.append(int(lg[len(inputs) - 1].argmax()))
        inputs.append(served[-1])
    outcome = {"samples": [{"sid": 0, "start": start, "max_new": 8,
                            "first": first, "served": served}],
               "lost": 0, "failed": 0}
    checks = es.check(cfg, {}, 9, outcome)
    assert checks["max_logit_gap"][0] == 0.0
    bad = dict(outcome, samples=[dict(outcome["samples"][0],
                                      served=served[:3] + [(served[3] + 1)
                                                           % 300]
                                      + served[4:])])
    checks = es.check(cfg, {}, 9, bad)
    assert checks["max_logit_gap"][0] > 0.01
