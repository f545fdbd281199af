"""The program's telemetry on the profiler's clock.

Telemetry spans open ``jax.profiler.TraceAnnotation`` s of the same name
while telemetry is enabled, so a profiler trace holds them on its host
plane at the tracer's own timestamps; JAX's trace, lower and compile
times arrive as ``jax.*`` complete spans; the device transport and the
serving engine time their host paths (``transport.stage`` /
``transport.unstage``, ``serve.stack`` / ``serve.unstack`` /
``serve.admit``) and count the dense send buffer (``buffer_bytes``).
Disabled, none of it runs.  Last, the benchmark's readers of these
spans and counters find numbers in a tiny traced run of each cell.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CollectiveMoveManager, DeviceTransport, DistArray,
                        LongRange, PlaceGroup, telemetry, transport)
from repro.kernels import ops
from repro.kernels import reloc_codec

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


@pytest.fixture
def codec_backend(monkeypatch):
    def use(name):
        monkeypatch.setattr(ops, "_BACKEND", name)
    return use


def _window(places=3, moves=((0, 1, 5), (2, 1, 3)), mm=None, col=None):
    """One relocation window of float32 rows 16 bytes wide: ``moves``
    are ``(src, dest, rows)``, each taken from the front of ``src``'s
    block of 8 rows."""
    g = PlaceGroup(places)
    if col is None:
        col = DistArray(g, track=False)
        data = np.arange(places * 8 * 4, dtype=np.float32).reshape(-1, 4)
        for p in range(places):
            col.add_chunk(p, LongRange(8 * p, 8 * p + 8),
                          data[8 * p:8 * p + 8])
    if mm is None:
        mm = CollectiveMoveManager(g, transport=DeviceTransport())
    for src, dest, m in moves:
        col.move_range_at_sync(LongRange(8 * src, 8 * src + m), dest, mm)
    mm.sync()
    return col, mm


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------
def _host_events(tdir):
    """``{name: [absolute start ns, ...]}`` of the host planes' events
    in the trace under ``tdir``."""
    from jax.profiler import ProfileData

    path = sorted(Path(tdir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    base = None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            base = dict(plane.stats)["profile_start_time"]
    assert base is not None
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(base + e.start_ns)
    return out


def test_spans_land_on_the_profiler_host_plane_at_their_own_time(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        telemetry.enable()
        with telemetry.span("probe.outer"):
            with telemetry.span("probe.inner"):
                jnp.ones(8).block_until_ready()
        _window()
        telemetry.disable()
    finally:
        jax.profiler.stop_trace()
    host = _host_events(tmp_path)
    recs = [r for r in telemetry.tracer().records() if r["ph"] == "X"]
    names = {r["name"] for r in recs}
    for name in ("probe.outer", "probe.inner", "reloc.phase1",
                 "transport.exchange", "transport.stage",
                 "transport.unstage"):
        assert name in names and name in host, name
    for r in recs:
        if r["name"] in host:
            gap = min(abs(t - r["ts"] * 1e3) for t in host[r["name"]])
            assert gap < 1e6, (r["name"], gap)


def _count_annotations(monkeypatch) -> list:
    """Names of the profiler annotations built from here on."""
    made = []
    real = jax.profiler.TraceAnnotation

    class Counting(real):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    return made


def test_complete_spans_reach_the_ring_buffer_only(monkeypatch):
    made = _count_annotations(monkeypatch)
    telemetry.enable()
    now = telemetry.now_us()
    telemetry.complete("after.the.fact", now - 5.0, now)
    with telemetry.span("live"):
        pass
    assert made == ["live"]
    assert {r["name"] for r in telemetry.tracer().records()} \
        == {"after.the.fact", "live"}


def test_disabled_window_builds_no_annotation_and_records_nothing(
        monkeypatch, codec_backend):
    import jax.monitoring

    made, listeners = _count_annotations(monkeypatch), []
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        lambda fn: listeners.append(fn))
    for name in ("xla", "pallas_interpret"):
        codec_backend(name)
        _window()
        telemetry._on_compile_event(
            "/jax/core/compile/backend_compile_duration", 0.5)
    assert made == [] and listeners == []
    assert telemetry.tracer().records() == []
    assert telemetry.metrics_dict() == {}
    # the same window, enabled, annotates every span it records
    telemetry.enable()
    _window()
    # (but the complete spans: reloc.window, and jax.* if it compiled)
    recorded = [r["name"] for r in telemetry.tracer().records()
                if r["ph"] == "X" and r["name"] != "reloc.window"
                and not r["name"].startswith("jax.")]
    assert sorted(made) == sorted(recorded)
    assert {"transport.stage", "transport.unstage"} <= set(made)


def test_fresh_jit_records_trace_lower_and_compile_spans():
    telemetry.enable()
    salt = float(np.random.default_rng().integers(1, 1 << 30))

    def fresh_program(x):
        return x * salt + 1.0

    jax.jit(fresh_program)(jnp.arange(7.0)).block_until_ready()
    spans = {}
    for r in telemetry.tracer().records():
        spans.setdefault(r["name"], []).append(r)
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        assert name in spans, sorted(spans)
        assert all(r["ph"] == "X" and r["dur"] >= 0 for r in spans[name])
    # disabled, the listener stays registered but records nothing
    telemetry.disable()
    telemetry.reset()
    jax.jit(lambda x: x * (salt + 1.0))(jnp.arange(5.0))
    assert telemetry.tracer().records() == []


# ---------------------------------------------------------------------------
# the dense send buffer counter
# ---------------------------------------------------------------------------
# three places, 0 -> 1 five rows and 2 -> 1 three rows of 16 bytes:
# fused: 9 pairs x 8 slots (pow2 of the busiest pair) x 16 B;
# masked: 3 places x 8 slots (pow2 of the busiest receiver, 8) x 16 B
@pytest.mark.parametrize("name,want", [("pallas_interpret", 9 * 8 * 16),
                                       ("xla", 3 * 8 * 16)])
def test_buffer_bytes_is_exact_merges_and_rides_the_span(
        codec_backend, name, want):
    codec_backend(name)
    telemetry.enable()
    col, mm = _window()
    st = mm.last_transport_stats
    assert st.codec_backend == name
    assert st.row_bytes == 8 * 16
    assert st.buffer_bytes == want
    assert st.as_dict()["buffer_bytes"] == want
    ex = [r["args"] for r in telemetry.tracer().records()
          if r["name"] == "transport.exchange"]
    assert len(ex) == 1
    assert (ex[0]["row_bytes"], ex[0]["buffer_bytes"]) == (8 * 16, want)
    # a second window on the same transport merges into its lifetime
    _window(moves=((1, 2, 2),), mm=mm, col=col)
    lifetime = mm.transport.lifetime
    assert lifetime.buffer_bytes == want + mm.last_transport_stats.buffer_bytes
    assert telemetry.metrics_dict()["transport.device.buffer_bytes"] \
        == lifetime.buffer_bytes


def test_fused_buffer_bytes_counts_every_round(codec_backend, monkeypatch):
    # two slots of 16 B per pair a round: the busiest pair's 5 rows take
    # three rounds of 9 pairs x 2 slots
    codec_backend("pallas_interpret")
    monkeypatch.setattr(transport, "_EXCHANGE_BYTES", 9 * 16 * 2)
    _, mm = _window()
    st = mm.last_transport_stats
    assert st.exchanges == 3
    assert st.buffer_bytes == 9 * 2 * 16 * 3


# ---------------------------------------------------------------------------
# program names in a profile
# ---------------------------------------------------------------------------
def _module_name(lowered) -> str:
    return lowered.as_text().split("module @", 1)[1].split(" ", 1)[0]


def test_codec_and_transport_programs_have_stable_names():
    f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32
    sds = jax.ShapeDtypeStruct
    tab = sds((4 * 8,), i32)
    enc = reloc_codec._encode_pack_call(4, 8, 16, 5, 4, np.float32, True)
    assert _module_name(enc.lower(sds((5, 4), f32), tab, tab)) \
        == "jit_reloc_encode_pack"
    pack = reloc_codec._pack_rows_call(4, 8, 16, 20, True)
    assert _module_name(pack.lower(sds((20,), u32), tab, tab)) \
        == "jit_reloc_pack"
    dec = reloc_codec._decode_call(5, 4, 16, np.float32, True)
    assert _module_name(dec.lower(sds((5, 4), u32))) == "jit_reloc_decode"
    t = DeviceTransport()
    fused = t._fused_exchange_fn(2, 8, 16)
    assert _module_name(fused.lower(sds((2, 2, 1, 1, 128), u32))) \
        == "jit_transport_all_to_all"
    masked = t._exchange_fn(2, 8, 16)
    assert _module_name(masked.lower(sds((2, 8, 16), jnp.uint8),
                                     sds((2, 2), i32))) \
        == "jit_transport_masked_all_to_all"


# ---------------------------------------------------------------------------
# the serving engine's spans
# ---------------------------------------------------------------------------
def test_serving_round_opens_stack_unstack_and_admit_spans():
    from repro.core import GLBConfig
    from repro.serving import DecodeEngine
    from repro.serving.decode import serving_config
    from repro.serving.elastic import ElasticServingDriver

    engine = DecodeEngine(serving_config(n_layers=1, d_model=32, d_ff=64,
                                         vocab_size=64),
                          s_cache=16, max_batch=2)
    d = ElasticServingDriver(2, slots_per_replica=4,
                             glb=GLBConfig(period=1000), engine=engine)
    d.admit(3, 4)                       # untraced: compiles its bucket
    d.decode_round()
    telemetry.enable()
    sid = d.admit(2, 4)
    d.decode_round()
    spans = {}
    for r in telemetry.tracer().records():
        spans.setdefault(r["name"], []).append(r)
    assert [r["args"]["seq"] for r in spans["serve.admit"]] == [sid]
    assert spans["serve.stack"] and spans["serve.unstack"]
    # neither sits inside the measured decode span
    for batch in spans["serve.decode_batch"]:
        lo, hi = batch["ts"], batch["ts"] + batch["dur"]
        for name in ("serve.stack", "serve.unstack"):
            for r in spans[name]:
                assert r["ts"] + r["dur"] <= lo or r["ts"] >= hi


# ---------------------------------------------------------------------------
# the benchmark's readers on a tiny traced run of each cell
# ---------------------------------------------------------------------------
NEW_METRICS = {
    "reloc_ycsb_zipf": ("transport.stage_ms_per_window",
                        "transport.unstage_ms_per_window",
                        "transport.buffer_fill_share"),
    "serve_azure_conv": ("serve.batch_ms_per_round",
                         "serve.admit_ms_per_round",
                         "serve.compile_ms_per_round"),
}


# what the harness sets on JAX's config, put back after the runs
_HARNESS_CONFIG = ("jax_compilation_cache_dir",
                   "jax_persistent_cache_min_compile_time_secs",
                   "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(scope="module")
def traced_cells():
    from jax.experimental.compilation_cache import compilation_cache

    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness
    from bench.tests.conftest import tiny_cell

    saved = {k: getattr(jax.config, k) for k in _HARNESS_CONFIG}
    try:
        yield {name: harness.run(name, 7, 1.0, True, require_tpu=False,
                                 loaded=tiny_cell(name))
               for name in NEW_METRICS}
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("cell,metric", [(c, m) for c, ms in
                                         NEW_METRICS.items() for m in ms])
def test_new_readers_find_numbers_in_a_traced_run(traced_cells, cell,
                                                  metric):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [cell]
    r = traced_cells[cell]
    assert r["correct"], r["checks"]
    value = r["metrics"][metric]["value"]
    assert value >= 0
    if metric == "transport.buffer_fill_share":
        assert 0 < value <= 100
    if metric.endswith(("stage_ms_per_window", "batch_ms_per_round")):
        assert value > 0
