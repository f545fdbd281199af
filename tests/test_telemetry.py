"""Unified runtime telemetry (ISSUE 7): the ring-buffer tracer, the
metrics registry, and cross-rank trace aggregation.

Three layers under test: the primitives (span nesting, ring
wraparound + drop counter, the disabled-mode fast path, histogram
percentile accuracy vs numpy), the instrumentation wiring (an
in-process relocation window whose phase spans and transport exchange
all carry the same ``window`` correlation attr), and the multi-process
merge (a real 2-process ``run_multiprocess(collect_trace=True)`` run
whose single returned timeline holds both ranks' transport exchange
spans with consistent per-window sequence tags).
"""
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import (CollectiveMoveManager, DistArray, DistributedTransport,
                        HostTransport, LongRange, PlaceGroup,
                        ProcessPlaceGroup, run_multiprocess, telemetry)
from repro.core.transport import TransportStats


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts disabled with empty buffers and leaves the
    module state the same way (the flag is process-global)."""
    telemetry.disable()
    telemetry.reset()
    telemetry.set_rank(0)
    yield
    telemetry.disable()
    telemetry.reset()
    telemetry.set_rank(0)


# ---------------------------------------------------------------------------
# Primitives: spans
# ---------------------------------------------------------------------------
class TestSpans:
    def test_disabled_mode_is_a_null_fast_path(self):
        assert not telemetry.enabled()
        sp = telemetry.span("x", a=1)
        assert sp is telemetry.NULL_SPAN
        assert not sp                       # falsy: guards attr formatting
        assert sp.set(bytes=1) is sp        # no-op, chainable
        with sp:
            pass
        telemetry.event("e", k=1)
        telemetry.observe("h", 1.0)
        telemetry.inc("c")
        telemetry.gauge("g", 2)
        assert telemetry.tracer().records() == []
        assert telemetry.metrics_dict() == {}

    def test_span_records_and_nesting(self):
        telemetry.enable()
        with telemetry.span("outer", a=1) as sp:
            assert sp  # truthy when live
            with telemetry.span("inner"):
                pass
            sp.set(b=2)
        recs = telemetry.tracer().records()
        # inner exits (and records) first
        assert [r["name"] for r in recs] == ["inner", "outer"]
        inner, outer = recs
        assert inner["ph"] == outer["ph"] == "X"
        # containment: the inner span nests inside the outer
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
        assert outer["args"] == {"a": 1, "b": 2}

    def test_span_tags_error_class_on_exception(self):
        telemetry.enable()
        with pytest.raises(ValueError):
            with telemetry.span("boom"):
                raise ValueError("nope")
        (rec,) = telemetry.tracer().records()
        assert rec["args"]["error"] == "ValueError"

    def test_ring_wraparound_and_drop_counter(self):
        telemetry.enable(capacity=8)
        for i in range(20):
            telemetry.event("e", i=i)
        tr = telemetry.tracer()
        recs = tr.records()
        assert len(recs) == 8
        assert tr.dropped == 12
        # the oldest 12 were overwritten: records 12..19 survive, in order
        assert [r["args"]["i"] for r in recs] == list(range(12, 20))
        assert all(r["ph"] == "i" and r["s"] == "t" for r in recs)
        # restore default capacity for later tests
        telemetry.enable(capacity=65536)

    def test_context_attrs_tag_spans_and_events(self):
        telemetry.enable()
        with telemetry.context(window=7):
            with telemetry.span("s"):
                pass
            telemetry.event("e")
            with telemetry.context(window=8, extra=1):
                telemetry.event("e2")
            telemetry.event("e3")
        telemetry.event("outside")
        s, e, e2, e3, out = telemetry.tracer().records()
        assert s["args"] == {"window": 7}
        assert e["args"] == {"window": 7}
        assert e2["args"] == {"window": 8, "extra": 1}   # nested overrides
        assert e3["args"] == {"window": 7}               # restored
        assert "args" not in out

    def test_place_attr_and_thread_ordinals_pick_tracks(self):
        telemetry.enable()
        with telemetry.span("a", place=3):
            pass
        with telemetry.span("b"):
            pass
        t = threading.Thread(target=lambda: telemetry.event("c"))
        t.start()
        t.join()
        a, b, c = telemetry.tracer().records()
        assert a["tid"] == 3                  # place attr wins
        assert b["tid"] >= 1000               # thread ordinal track
        assert c["tid"] >= 1000 and c["tid"] != b["tid"]
        assert a["pid"] == b["pid"] == 0      # rank

    def test_complete_assembles_cross_thread_spans(self):
        telemetry.enable()
        t1 = telemetry.now_us()
        telemetry.complete("win", t1, t1 + 250.0, window=4)
        (rec,) = telemetry.tracer().records()
        assert rec["ph"] == "X"
        assert rec["dur"] == pytest.approx(250.0)
        assert rec["args"]["window"] == 4


# ---------------------------------------------------------------------------
# Primitives: metrics
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_counter_gauge_roundtrip(self):
        telemetry.enable()
        telemetry.inc("c", 2)
        telemetry.inc("c")
        telemetry.gauge("g", 7.5)
        d = telemetry.metrics_dict()
        assert d["c"] == 3
        assert d["g"] == 7.5

    def test_histogram_percentiles_match_numpy(self):
        rng = np.random.default_rng(7)
        samples = rng.lognormal(mean=-6.0, sigma=1.5, size=20_000)
        h = telemetry.Histogram()
        for v in samples:
            h.observe(v)
        for p in (50, 95, 99):
            exact = float(np.percentile(samples, p))
            est = h.percentile(p)
            # log-bucket growth of 5.5% bounds the relative error
            assert abs(est - exact) / exact < 0.06, (p, est, exact)
        assert h.count == len(samples)
        assert h.mean == pytest.approx(float(samples.mean()), rel=1e-9)
        d = h.as_dict("m")
        assert d["m.min"] == pytest.approx(float(samples.min()))
        assert d["m.max"] == pytest.approx(float(samples.max()))
        assert set(d) == {"m.count", "m.sum", "m.mean", "m.min", "m.max",
                          "m.p50", "m.p95", "m.p99"}

    def test_histogram_empty_and_zero_values(self):
        h = telemetry.Histogram()
        assert h.as_dict("m") == {"m.count": 0}
        assert h.percentile(50) == 0.0
        h.observe(0.0)          # at-or-below-LO values land in bin 0
        assert h.count == 1
        assert h.percentile(99) == 0.0   # clamped into [vmin, vmax]

    def test_registry_publisher_polled_at_read_time(self):
        telemetry.enable()
        stats = TransportStats(kind="host")
        telemetry.metrics().add_publisher("k", stats.publish)
        stats.payloads = 5
        stats.wire_bytes = 640
        d = telemetry.metrics_dict()
        assert d["transport.host.payloads"] == 5
        assert d["transport.host.wire_bytes"] == 640
        stats.payloads = 9      # registry polls cumulative state fresh
        assert telemetry.metrics_dict()["transport.host.payloads"] == 9
        telemetry.reset()       # clears publishers too
        assert "transport.host.payloads" not in telemetry.metrics_dict()

    def test_transport_stats_merge_and_as_dict(self):
        a = TransportStats(kind="device", payloads=2, local=1, rows=10,
                           row_bytes=80, wire_bytes=128,
                           pad_waste_bytes=48, buffer_bytes=512, width=16,
                           exchanges=1, codec_backend="xla")
        b = TransportStats(kind="device", payloads=3, rows=5, row_bytes=40,
                           wire_bytes=64, pad_waste_bytes=24,
                           buffer_bytes=256, width=8, exchanges=2,
                           codec_backend="pallas_interpret")
        out = a.merge(b)
        assert out is a                     # merge returns self
        assert (a.payloads, a.local, a.rows) == (5, 1, 15)
        assert (a.row_bytes, a.wire_bytes, a.exchanges) == (120, 192, 3)
        assert a.pad_waste_bytes == 72
        assert a.buffer_bytes == 768
        assert a.width == 16                # high-water mark, not a sum
        assert a.codec_backend == "pallas_interpret"   # latest window
        # an empty backend never clobbers a recorded one
        a.merge(TransportStats(kind="device"))
        assert a.codec_backend == "pallas_interpret"
        d = a.as_dict("t.")
        assert d == {"t.payloads": 5, "t.local": 1, "t.rows": 15,
                     "t.row_bytes": 120, "t.wire_bytes": 192,
                     "t.pad_waste_bytes": 72, "t.buffer_bytes": 768,
                     "t.width": 16,
                     "t.exchanges": 3,
                     "t.codec_backend": "pallas_interpret"}


# ---------------------------------------------------------------------------
# Export
# ---------------------------------------------------------------------------
class TestExport:
    def test_chrome_trace_shape_and_normalization(self, tmp_path):
        telemetry.enable()
        with telemetry.span("a"):
            pass
        telemetry.event("b")
        doc = telemetry.write_chrome_trace(tmp_path / "t.json")
        import json
        on_disk = json.loads((tmp_path / "t.json").read_text())
        assert on_disk == doc
        assert doc["displayTimeUnit"] == "ms"
        assert doc["otherData"]["dropped_spans"] == 0
        evs = doc["traceEvents"]
        assert len(evs) == 2
        assert min(e["ts"] for e in evs) == 0.0   # normalized to t0
        assert {e["ph"] for e in evs} == {"X", "i"}

    def test_phase_breakdown_aggregates_complete_spans(self):
        telemetry.enable()
        for _ in range(3):
            with telemetry.span("phase.a"):
                pass
        telemetry.event("not.a.span")
        bd = telemetry.phase_breakdown()
        assert set(bd) == {"phase.a"}
        assert bd["phase.a"]["spans"] == 3
        assert bd["phase.a"]["total_us"] >= bd["phase.a"]["mean_us"]

    def test_obs_package_reexports_the_api(self):
        assert obs.span is telemetry.span
        assert obs.enable is telemetry.enable
        assert obs.Tracer is telemetry.Tracer
        assert obs.metrics_dict is telemetry.metrics_dict
        assert obs.write_chrome_trace is telemetry.write_chrome_trace


# ---------------------------------------------------------------------------
# Instrumentation wiring: one in-process relocation window
# ---------------------------------------------------------------------------
N_PLACES = 4
N_ROWS = 16
WIDTH = 3


def _one_window(g, transport):
    rows = np.arange(N_ROWS * WIDTH, dtype=np.float64).reshape(N_ROWS, WIDTH)
    col = DistArray(g, track=True)
    for p, r in enumerate(LongRange(0, N_ROWS).split(N_PLACES)):
        if g.is_local(p) and r.size:
            col.add_chunk(p, r, rows[r.start:r.end])
    mm = CollectiveMoveManager(g, transport=transport)
    col.move_range_at_sync(LongRange(2, 6), 3, mm)
    # enqueue() before finish(): delivery runs on the background thread,
    # so the window exercises the full span set (incl. reloc.enqueue)
    mm.sync_async((col,)).enqueue().finish()
    return col, mm


class TestRelocationInstrumentation:
    def test_window_spans_share_the_window_correlation_attr(self):
        telemetry.enable()
        _one_window(PlaceGroup(N_PLACES), HostTransport())
        recs = telemetry.tracer().records()
        by_name = {}
        for r in recs:
            by_name.setdefault(r["name"], []).append(r)
        for name in ("reloc.phase1", "reloc.deliver", "reloc.commit",
                     "reloc.window", "transport.exchange", "reloc.enqueue"):
            assert name in by_name, f"missing {name} in {sorted(by_name)}"
        wid = by_name["reloc.window"][0]["args"]["window"]
        # the phase spans and the transport exchange inside phase 1 all
        # carry the same window id — the cross-thread correlation key
        for name in ("reloc.phase1", "reloc.deliver", "transport.exchange",
                     "reloc.enqueue"):
            assert by_name[name][0]["args"]["window"] == wid, name
        ex = by_name["transport.exchange"][0]["args"]
        assert ex["kind"] == "host"
        assert ex["seq"] == 0
        # the exchange's own numbers ride its span
        assert ex["payloads"] >= 1
        assert ex["wire_bytes"] == ex["row_bytes"] == ex["buffer_bytes"] == 0
        # metrics landed alongside the spans; the exchange keeps no
        # per-exchange histogram, only the lifetime counters
        m = telemetry.metrics_dict()
        assert m["reloc.window_s.count"] == 1
        assert m["reloc.window_bytes.count"] == 1
        assert not any(k.startswith("transport.exchange_") for k in m)
        assert m["transport.host.payloads"] >= 1

    def test_traced_steal_windows_export_spans_and_metric_keys(self,
                                                               tmp_path):
        # the host steal loop on the device transport: a relocation
        # window per steal, each exchange on the jitted all_to_all
        import json
        from repro.core import (DeviceTransport, DistArrayWorkload,
                                GLBConfig, GlobalLoadBalancer)

        telemetry.enable()
        g = PlaceGroup(N_PLACES)
        col = DistArray(g, track=True)
        col.add_chunk(0, LongRange(0, N_ROWS),
                      np.arange(N_ROWS * WIDTH, dtype=np.float32)
                      .reshape(N_ROWS, WIDTH))
        for p in g.members:
            col.handle(p)
        glb = GlobalLoadBalancer(
            g, DistArrayWorkload(col, transport=DeviceTransport()),
            GLBConfig(random_steal_attempts=0))
        assert glb.steal_loop(max_rounds=4)["stolen"] > 0
        telemetry.write_chrome_trace(tmp_path / "trace.json")
        doc = json.loads((tmp_path / "trace.json").read_text())
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        names = {e["name"] for e in spans}
        assert {"reloc.window", "transport.exchange"} <= names, names
        # each exchange's wire bytes ride its span
        assert any(e["args"].get("wire_bytes", 0) > 0 for e in spans
                   if e["name"] == "transport.exchange")
        m = telemetry.metrics_dict()
        for key in ("reloc.window_s.p50", "reloc.window_s.p95",
                    "glb.overlap_fraction", "transport.device.wire_bytes"):
            assert key in m, f"missing metric {key}"
        assert m["transport.device.wire_bytes"] > 0

    def test_uninstrumented_run_records_nothing(self):
        _one_window(PlaceGroup(N_PLACES), HostTransport())
        assert telemetry.tracer().records() == []
        assert telemetry.metrics_dict() == {}


# ---------------------------------------------------------------------------
# Cross-rank aggregation (module-level worker: spawn pickles by reference)
# ---------------------------------------------------------------------------
def _trace_worker(backend):
    g = ProcessPlaceGroup(N_PLACES, backend)
    col, mm = _one_window(g, DistributedTransport())
    return {"rank": backend.rank,
            "owner_of_3": col.get_distribution().owner_of(3)}


class TestCrossRankAggregation:
    def test_inline_single_process_collect_trace(self):
        results, timeline = run_multiprocess(_trace_worker, 1,
                                             collect_trace=True)
        assert results[0]["rank"] == 0
        assert any(r["name"] == "transport.exchange" for r in timeline)

    def test_two_process_merged_timeline(self):
        results, timeline = run_multiprocess(_trace_worker, 2,
                                             collect_trace=True)
        assert [r["rank"] for r in results] == [0, 1]
        assert all(r["owner_of_3"] == 3 for r in results)
        # one merged, rank-tagged timeline: both ranks' exchanges present
        ex = [r for r in timeline if r["name"] == "transport.exchange"]
        by_rank = {0: [], 1: []}
        for r in ex:
            by_rank[r["pid"]].append(r)
        assert by_rank[0] and by_rank[1]
        # the exchange is collective and program-ordered, so the two
        # ranks' sequence tags line up one-to-one
        seqs0 = sorted(r["args"]["seq"] for r in by_rank[0])
        seqs1 = sorted(r["args"]["seq"] for r in by_rank[1])
        assert seqs0 == seqs1
        assert all(r["args"]["kind"] == "distributed" for r in ex)
        # timestamps are sorted (the merge contract)
        ts = [r["ts"] for r in timeline]
        assert ts == sorted(ts)
        # window spans from both ranks in the one timeline
        wins = [r for r in timeline if r["name"] == "reloc.window"]
        assert {r["pid"] for r in wins} == {0, 1}
