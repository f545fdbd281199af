"""Run one cell of ``BENCHMARK.json`` once: set-up, the measured window,
the comparison that decides ``correct``, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``: the configuration; its ``system`` key
  names the driver ``bench/systems/<system>.py``;
* ``bench/traffic/<traffic>.json``: the mix, whose ``kind`` names its
  generator ``bench/generators/<kind>.py``;
* ``bench/metrics/<metric>.py``: a reader, ``read(obs) -> float | None``
  (``<base>.<kind>`` may share ``<base>.py``).

A driver module provides ``KERNELS`` (kernel names the trace reduction
sums), ``System(config, mix, seed)`` with ``warm()``,
``run_window(seconds) -> dict`` and ``finish() -> outcome`` (reads what
the comparison needs, after which the program's state is dropped), and
``check(config, mix, seed, outcome, control=False) -> checks`` with
``checks`` as ``{name: (value, limit)}``.  With ``control`` the check
puts the reference, one precision down, in the program's place, and
the run's ``correct`` is the control's: a sound benchmark reads false.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import logging
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# a traced run measures at most this many seconds: traces are large, and
# reading them has to fit in the run's time
TRACE_WINDOW_S = 10.0


class CellError(RuntimeError):
    """The cell cannot run here (no chip, unknown device, bad files)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, benchmark: dict | None = None) -> dict:
    spec = benchmark if benchmark is not None else \
        load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; cells: "
                        f"{sorted(cells)}")
    cell = cells[workload]
    return {"benchmark": spec, "cell": cell,
            "config": load_json(BENCH / "configs" / f"{cell['config']}.json"),
            "mix": load_json(BENCH / "traffic" / f"{cell['traffic']}.json")}


def cell_metrics(spec: dict, workload: str, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(name: str):
    """``bench/metrics/<name>.py``; a metric ``<base>.<kind>`` split by
    the end-to-end metric it moves may share ``<base>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Observed:
    """What a per-layer reader reads: the window's host spans from the
    program's telemetry, the driver's counters, the reduced device trace
    and the peak table."""

    config: dict
    mix: dict
    window_s: float
    counters: dict
    spans: list = field(default_factory=list)
    trace: dict | None = None
    peaks: dict = field(default_factory=dict)

    def span_s(self, name: str) -> float:
        return sum(s.get("dur", 0.0) for s in self.spans
                   if s["name"] == name) * 1e-6


class _Compiles:
    """Backend compiles, counted from JAX's monitoring events."""

    count = 0
    _on = False

    @classmethod
    def install(cls) -> None:
        if cls._on:
            return
        import jax

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        cls._on = True


class _WindowCompiles(logging.Handler):
    """Programs JAX lowers and compiles while the window runs: with the
    persistent cache a program may be loaded rather than compiled, but
    its tracing and lowering still stall the host."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.names: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            name, _, shapes = msg[10:].partition(" with global shapes and types ")
            self.names.append(f"{name} {shapes.split('. Argument')[0]}"[:200])

    def __enter__(self):
        import jax

        self._was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._log = logging.getLogger("jax._src.interpreters.pxla")
        self._propagate = self._log.propagate
        self._log.propagate = False
        self._log.addHandler(self)
        return self

    def __exit__(self, *exc):
        import jax

        self._log.removeHandler(self)
        self._log.propagate = self._propagate
        jax.config.update("jax_log_compiles", self._was)
        return False


def enable_compile_cache() -> str:
    """The program's persistent cache (``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set), keeping every program so a
    second run of a cell compiles nothing."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise CellError(f"the program is not at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro import compile_cache

    where = compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def device_info(chips: int, require_tpu: bool) -> tuple[dict, dict]:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise CellError(f"no TPU: JAX found {devs[0].platform}")
    if require_tpu and len(devs) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX found "
                        f"{len(devs)}")
    table = load_json(BENCH / "peaks.json")["devices"]
    kind = devs[0].device_kind
    if kind in table:
        peaks = table[kind]
    elif require_tpu:
        raise CellError(f"device kind {kind!r} is not in bench/peaks.json")
    else:   # a CPU rehearsal: the arithmetic runs, nothing is a device number
        peaks = next(iter(table.values()))
    return {"platform": devs[0].platform, "kind": kind,
            "count": len(devs)}, peaks


def _peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, control: bool = False,
        trace_dir: str | None = None, t0: float | None = None,
        loaded: dict | None = None, log=sys.stderr) -> dict:
    """One run of one cell; returns the result object."""
    t0 = time.perf_counter() if t0 is None else t0
    c = loaded if loaded is not None else load_cell(workload)
    spec, cell, config, mix = c["benchmark"], c["cell"], c["config"], c["mix"]
    enable_compile_cache()
    import jax

    device, peaks = device_info(int(cell["chips"]), require_tpu)
    _Compiles.install()
    c0 = _Compiles.count
    driver = importlib.import_module(f"bench.systems.{config['system']}")
    from repro.core import telemetry

    system = driver.System(config, mix, seed)
    system.warm()
    compiles_setup = _Compiles.count - c0
    setup_s = time.perf_counter() - t0
    tdir = None
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir, profiler_options=_trace_options())
        telemetry.enable()
        telemetry.tracer().clear()
    print("bench: the measured window starts", file=log, flush=True)
    w0 = telemetry.now_us()
    with _WindowCompiles() as lowered, \
            jax.profiler.TraceAnnotation("bench.traced"):
        e2e = system.run_window(min(seconds, TRACE_WINDOW_S) if trace
                                else seconds)
    w1 = telemetry.now_us()
    print("bench: the measured window ends", file=log, flush=True)
    compiles_window = _Compiles.count - c0 - compiles_setup
    spans = []
    if trace:
        jax.profiler.stop_trace()
        spans = [r for r in telemetry.tracer().records()
                 if r.get("ph") == "X" and w0 <= r["ts"] <= w1]
        telemetry.disable()
    device["memory_peak_bytes"] = _peak_bytes(int(cell["chips"]))
    counters = e2e.pop("counters")
    print(json.dumps({"cell": workload, "seed": seed, "setup_s": setup_s,
                      "compiles_setup": compiles_setup,
                      "compiles_window": compiles_window,
                      "lowered_in_window": len(lowered.names),
                      **{k: v for k, v in counters.items()
                         if isinstance(v, (int, float))}}), flush=True)

    for name in sorted(set(lowered.names)):
        print(f"bench: lowered in the window: {name} "
              f"x{lowered.names.count(name)}", file=log)
    outcome = system.finish()
    del system
    gc.collect()
    own = driver.check(config, mix, seed, outcome) if control else None
    checks = driver.check(config, mix, seed, outcome, control=control)
    correct = all(v <= lim for v, lim in checks.values())

    result = {"correct": correct, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": {}, "device": device}
    if trace:
        from bench import trace_reduce

        paths = sorted(Path(tdir).rglob("*.xplane.pb"))
        summary = trace_reduce.reduce(paths[-1], kernels=driver.KERNELS)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        obs = Observed(config, mix, (w1 - w0) * 1e-6, counters, spans,
                       summary, peaks)
        for m in cell_metrics(spec, workload, "per_layer"):
            value = load_reader(m["name"])(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        values = dict(e2e["metrics"], setup_s=setup_s)
        for m in cell_metrics(spec, workload, "end_to_end"):
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    if control:
        # the program's own numbers, beside the control's that decide
        result["program_checks"] = {k: {"value": v, "limit": lim}
                                    for k, (v, lim) in own.items()}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=log)
    log.flush()
    return result


def main(argv=None, t0: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the low-precision control in the "
                         "program's place; its correct must read false")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), control=bool(args.control),
                     trace_dir=args.trace_dir, t0=t0)
    except CellError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
