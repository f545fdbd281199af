"""The trace reduction on a small recorded trace, and the FLOP and byte
counters against numbers worked out by hand.

``data/codec_small.xplane.pb`` was recorded on one TPU v5e by
``data/record_trace.py``: inside a ``bench.traced`` span of about 313 ms,
three ``bench.window`` spans of about 93 ms, each one relocation window
of the ``reloc_ycsb_zipf`` mix (16 blocks of 512 records; one
``reloc_pack_rows`` kernel of about 12.4 ms and a ``reloc_decode_rows``
kernel), each followed by a ``bench.host_sleep`` span of about 11 ms
with the chip idle.  The checkout's directory in the trace's source
locations reads ``<checkout>/``.
"""
import json
from pathlib import Path

import pytest

from bench import counts
from bench import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "codec_small.xplane.pb"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.fixture(scope="module")
def summary():
    return tr.reduce(DATA, kernels=("reloc_pack_rows", "reloc_decode_rows",
                                    "no_such_kernel"))


def test_names():
    assert tr.op_name("%reloc_pack_rows.1 = u32[64,256,1,128]{3,2,1,0} "
                      "custom-call(s32[64,1,128] %bitcast.8)") \
        == "reloc_pack_rows"
    assert tr.op_name("%fusion = f32[8] fusion(f32[8] %p)") == "fusion"
    assert tr.module_name("jit_serve_step(8890894002672909697)") \
        == "jit_serve_step"


def test_busy_and_idle(summary):
    # the window is the bench.traced span
    assert summary["devices"] == 1
    assert 0.31 < summary["window_s"] < 0.32
    # three pack kernels of ~12.4 ms and a few small ops
    assert 0.040 < summary["busy_s"] < 0.050
    idle = sum(s for _, s in summary["idle_gaps"])
    assert idle == pytest.approx(summary["window_s"] - summary["busy_s"],
                                 rel=1e-9)


def test_no_window_span_is_an_error(monkeypatch):
    monkeypatch.setattr(tr, "WINDOW_SPAN", "bench.no_such_span")
    with pytest.raises(ValueError, match="marks the window"):
        tr.reduce(DATA)


def test_kernel_and_program_time(summary):
    k = summary["kernel_s"]
    assert 0.035 < k["reloc_pack_rows"] < 0.040
    assert 0 < k["reloc_decode_rows"] < 1e-3
    assert k["no_such_kernel"] == 0.0
    m = summary["module_s"]
    assert set(m) == {"jit_run", "jit_reshape", "jit_per_shard",
                      "jit_dynamic_slice", "jit_squeeze"}
    assert m["jit_run"] >= k["reloc_pack_rows"] + k["reloc_decode_rows"]
    top = dict(summary["device_ops"])
    assert top["jit_run/reloc_pack_rows"] == k["reloc_pack_rows"]
    assert len(summary["device_ops"]) <= 10


def test_gaps_by_host_span(summary):
    gaps = dict(summary["idle_gaps"])
    assert set(gaps) == {"bench.sync", "bench.host_sleep"}
    # the sleeps' gaps are put down to the sleep where their middle
    # falls in it; the host work of the windows is inside bench.sync
    assert 0.010 < gaps["bench.host_sleep"] < 0.035
    assert gaps["bench.sync"] > 0.2


def test_qwen2_counts_by_hand():
    cfg = json.loads((CONFIGS / "qwen2_1_5b_elastic4.json").read_text())
    # per layer: q 1536*1536, k and v 1536*256 each, o 1536*1536,
    # MLP 3*1536*8960; 28 layers; tied head 151936*1536
    layer = 1536 * 1536 * 2 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert layer == 46_792_704
    matmul = 28 * layer + 151936 * 1536
    assert counts.qwen2_matmul_params(cfg) == matmul == 1_543_569_408
    # biases (1536 + 2 * 256) and two norms (2 * 1536) per layer, final norm
    assert counts.qwen2_param_count(cfg) \
        == matmul + 28 * (2048 + 3072) + 1536
    # one sequence's KV at 2048 keys: 2 * 2 heads * 128 * 2 B * 28 layers
    assert counts.qwen2_kv_bytes(cfg, 2048) == 58_720_256
    # a token at 100 keys: 2 flops per weight, 4*12*128*28 per key
    assert counts.qwen2_token_flops(cfg, 100) \
        == 2 * matmul + 4 * 12 * 128 * 28 * 100
    step = counts.qwen2_step_bytes(cfg, [10, 20])
    assert step == 2 * counts.qwen2_param_count(cfg) + 2 * 2 * 128 * 2 * 28 * 30


def test_codec_bytes_by_hand():
    # 7680 records of 1000 B: read and written by encode and by decode
    assert counts.codec_bytes(7680, 1000) == 30_720_000
