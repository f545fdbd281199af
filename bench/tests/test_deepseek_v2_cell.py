"""The latent-attention, sparse-expert serving cell at tiny sizes: a sound
run reads correct, the float8 control does not, the counts agree with
hand arithmetic at the published sizes, and every new per-layer reader
finds a number in a traced run.  Nothing here is a device number."""
import numpy as np
import pytest

from bench import counts_mla_moe as counts
from bench import harness

CELL = "serve_dsv2_lite_azure_conv"
NEW_METRICS = ("mfu.serve_mla_moe", "decode_step_roofline.mla_moe",
               "moe.held_hit_share", "device_idle.serve_mla_moe",
               "serve.kv_migration_ms_per_round.mla_moe")
# the published shape at a width a CPU test holds: 1 dense + 2 expert
# layers, 16 routed experts of which 4 are held, top-3, one shared expert
TINY_DSV2 = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 intermediate_size=128, moe_intermediate_size=32,
                 num_hidden_layers=3, n_routed_experts=4,
                 num_experts_per_tok=3, n_shared_experts=1, vocab_size=300,
                 num_key_value_heads=4)


def tiny_dsv2_cell() -> dict:
    c = harness.load_cell(CELL)
    serving = dict(c["config"]["serving"], s_cache=64, max_batch=4,
                   slots_per_replica=8)
    deployment = dict(c["config"]["deployment"], routed_experts=16)
    # the tiny model's limit, as the published one means nothing at these
    # widths: over five seeds (CPU) the bf16 program's gap read 0-0.046,
    # the float8 control's 0.149-0.505
    c["config"] = dict(c["config"], **TINY_DSV2, serving=serving,
                       deployment=deployment,
                       correct_limits={"max_logit_gap": 0.1})
    c["mix"] = dict(c["mix"], population=8, warmup_rounds=8,
                    prompt={"median": 20, "sigma": 0.5},
                    output={"median": 6, "sigma": 0.5},
                    warm_migrations=[1, 2])
    return c


def _run(seed=7, seconds=1.0, trace=False, control=False):
    return harness.run(CELL, seed, seconds, trace, require_tpu=False,
                       loaded=tiny_dsv2_cell(), control=control)


def test_sound_run_is_correct_and_reads_the_host_metrics():
    """A CPU trace has no device plane, so the readers of device time
    find nothing here; the next test gives them a trace summary."""
    r = _run(seed=3000000019, trace=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    for name in ("mfu.serve_mla_moe", "moe.held_hit_share",
                 "serve.kv_migration_ms_per_round.mla_moe"):
        assert r["metrics"].get(name, {}).get("value") is not None, name
    assert 0 < r["metrics"]["moe.held_hit_share"]["value"] <= 100


def test_every_new_reader_reads_a_traced_window():
    cfg = _published()
    counters = {"calls": [[1000, 1200], [30]], "rounds": 4,
                "token_keys": [1000, 1200, 30], "moe.held_experts_hit": 300}
    trace = {"module_s": {"jit_serve_step": 0.05}, "devices": ["TPU:0"],
             "busy_s": 1.5, "window_s": 2.0}
    spans = [{"name": "transport.exchange", "dur": 8000.0}]
    obs = harness.Observed(cfg, {}, 2.0, counters, spans, trace,
                           {"hbm_bytes_per_s": 819e9,
                            "bf16_flops_per_s": 197e12})
    got = {name: harness.load_reader(name)(obs) for name in NEW_METRICS}
    need = (counts.step_bytes(cfg, [1000, 1200], 0)
            + counts.step_bytes(cfg, [30], 0)
            + 300 * counts.expert_bytes(cfg))
    want = {
        "mfu.serve_mla_moe": 100 * sum(counts.token_flops(cfg, k)
                                       for k in (1000, 1200, 30))
        / 2.0 / 197e12,
        "decode_step_roofline.mla_moe": 100 * need / 819e9 / 0.05,
        "moe.held_hit_share": 100 * 300 / (2 * 26 * 8),
        "device_idle.serve_mla_moe": 25.0,
        "serve.kv_migration_ms_per_round.mla_moe": 2.0,
    }
    for name in NEW_METRICS:
        assert got[name] == pytest.approx(want[name], rel=1e-9), name


def test_control_run_is_not_correct():
    r = _run(seconds=1.0, control=True)
    assert not r["correct"], r["checks"]
    assert all(v["value"] <= v["limit"]
               for v in r["program_checks"].values()), r["program_checks"]


def test_untraced_run_reports_end_to_end_metrics():
    r = _run(seconds=1.0)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"tokens_per_s", "round_p95_ms", "setup_s"}


def _published():
    """The configuration at the published depth: the file holds one
    pipeline stage's 14 of the 27 layers."""
    return dict(harness.load_cell(CELL)["config"], num_hidden_layers=27)


def test_the_file_cuts_only_what_reduced_lists():
    """Against the catalog's published config.json values that the file
    changes, each listed in the benchmark's ``reduced``."""
    import json

    from bench.harness import ROOT

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "deepseek_v2_lite_ep8_elastic4")
    published = {"num_hidden_layers": 27, "n_routed_experts": 64,
                 "max_position_embeddings": 163840}
    cfg = harness.load_cell(CELL)["config"]
    changed = sorted(k for k, v in published.items() if cfg[k] != v)
    assert changed == sorted(entry["reduced"])
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["deployment"]["routed_experts"] == 64


def test_counts_by_hand_at_the_published_sizes():
    cfg = _published()
    d, v = 2048, 102400
    mla = (d * 16 * 192 + d * 512 + d * 64 + 512 * 16 * 256
           + 16 * 128 * d + 512)
    assert mla == 13_763_072                      # 13.76 M a layer
    expert = 3 * d * 1408
    moe_layer = 8 * expert + 3 * d * 2816 + d * 64
    assert moe_layer == 86_638_592                # 69.2 + 17.3 + 0.13 M
    total = 2 * v * d + 27 * (mla + 2 * d) + d + 3 * d * 10944 + 26 * moe_layer
    assert counts.held_param_count(cfg) == total
    assert round(2 * total / 1e9, 2) == 6.22      # GB in bf16
    assert counts.kv_bytes_per_token(cfg) == 27 * (576 * 2 + 4) == 31_212
    assert round(2048 * 31_212 / 1e6, 1) == 63.9  # MB a sequence
    # a token at 0 keys: attention 2 x weights (absorbed), the dense
    # layer, shared experts and router, 0.75 routed experts, the head
    absorbed = d * 16 * 192 + d * 512 + d * 64 + 2 * 16 * 128 * 512 \
        + 16 * 128 * d
    want = (2 * 27 * absorbed + 2 * 3 * d * 10944
            + 2 * 26 * (3 * d * 2816 + d * 64 + 0.75 * expert) + 2 * d * v)
    assert counts.token_flops(cfg, 0) == int(want)
    assert counts.token_flops(cfg, 10) - counts.token_flops(cfg, 0) == \
        10 * 27 * 2 * 16 * (576 + 512)
    fixed = total - v * d - 26 * 8 * expert
    assert counts.step_bytes(cfg, [5, 7], 3) == \
        2 * fixed + 3 * 2 * expert + 12 * 31_212


def test_the_tiny_tree_is_the_programs_layout():
    import jax

    from bench import weights_mla_moe
    from bench.systems.elastic_serving_mla_moe import model_config
    from repro.models import zoo

    cfg = tiny_dsv2_cell()["config"]
    made = weights_mla_moe.deepseek_v2_params(cfg, 5, "bfloat16")
    want = zoo.abstract_params(model_config(cfg))
    assert jax.tree_util.tree_structure(made) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(made),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    router = np.asarray(made["scan"][0]["ffn"]["router"]["w"]["w"])
    assert router.dtype == np.float32
    assert np.array_equal(router.astype("bfloat16").astype(np.float32),
                          router)
    n = sum(a.size for a in jax.tree_util.tree_leaves(made))
    pad = 2 * (weights_mla_moe.vocab_padded(cfg) - cfg["vocab_size"]) \
        * cfg["hidden_size"]
    assert n == counts.held_param_count(cfg) + pad


@pytest.mark.parametrize("key,value", [("scoring_func", "sigmoid"),
                                       ("routed_scaling_factor", 2.5),
                                       ("tie_word_embeddings", True)])
def test_a_config_the_program_does_not_serve_is_refused(key, value):
    from bench.systems.elastic_serving_mla_moe import model_config

    with pytest.raises(ValueError):
        model_config(dict(_published(), **{key: value}))
