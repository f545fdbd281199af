"""Reduce one profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) with an
``XLA Modules`` line (one event per program run, named
``jit_<fn>(<hash>)``) and an ``XLA Ops`` line (one event per HLO
instruction run, named by its HLO text ``%<name>.<k> = ...``; a Pallas
kernel is a ``custom-call`` whose instruction carries the kernel's
``name``).  Host threads live on ``/host:CPU``; the benchmark's own
spans are ``jax.profiler.TraceAnnotation`` events named ``bench.*``
there, on the same clock.

:func:`reduce` returns, over the window that the ``bench.traced``
annotation marks (a trace without it is an error):

* ``busy_s``: the union of the op intervals, averaged over the chips;
* ``window_s``: the window's length;
* ``kernel_s``: device seconds of the ops whose instruction name starts
  with each requested kernel name;
* ``module_s``: device seconds of each program (``jit_<fn>``);
* ``device_ops``: the ten ``program/op`` names that took most time;
* ``idle_gaps``: idle seconds on the chips by the innermost ``bench.*``
  span the host was in at the middle of each gap, the ten largest.
"""
from __future__ import annotations

import bisect
import re

__all__ = ["reduce", "op_name", "module_name"]

WINDOW_SPAN = "bench.traced"
_OPS, _MODULES = "XLA Ops", "XLA Modules"


def op_name(event_name: str) -> str:
    """``%reloc_pack_rows.1 = u32[...] custom-call(...)`` -> ``reloc_pack_rows``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_name(event_name: str) -> str:
    """``jit_serve_step(8890894002672909697)`` -> ``jit_serve_step``."""
    return event_name.split("(", 1)[0]


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line, lo, hi):
    for e in line.events:
        s = float(e.start_ns)
        t = s + float(e.duration_ns)
        if t > lo and s < hi:
            yield e.name, max(s, lo), min(t, hi)


def _host_spans(planes):
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    s = float(e.start_ns)
                    spans.append((s, s + float(e.duration_ns), e.name))
    return spans


def _innermost(spans):
    """Boundaries ``cuts`` and ``labels`` such that the innermost span
    covering time ``t`` is ``labels[bisect_right(cuts, t)]``."""
    points = sorted({x for s, e, _ in spans for x in (s, e)})
    labels = ["outside bench spans"]
    by_start = sorted(spans)
    active, j = [], 0
    for t in points:
        while j < len(by_start) and by_start[j][0] <= t:
            active.append(by_start[j])
            j += 1
        active = [sp for sp in active if sp[1] > t]
        labels.append(min(active, key=lambda sp: sp[1] - sp[0])[2]
                      if active else "outside bench spans")
    return points, labels


def reduce(path, *, kernels=(), top: int = 10) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)
    spans = _host_spans(planes)
    marks = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not marks:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span marks the window")
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    cuts, labels = _innermost([(s, e, n) for s, e, n in spans
                               if n != WINDOW_SPAN])

    kernel_s = {k: 0.0 for k in kernels}
    module_s: dict[str, float] = {}
    op_s: dict[str, float] = {}
    gap_s: dict[str, float] = {}
    busy_total, used = 0.0, 0
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        if _OPS not in lines:
            continue
        mods = sorted((s, e, module_name(n))
                      for n, s, e in _events(lines[_MODULES], lo, hi)) \
            if _MODULES in lines else []
        for s, e, name in mods:
            module_s[name] = module_s.get(name, 0.0) + (e - s) * 1e-9
        starts = [m[0] for m in mods]
        busy = []
        for n, s, e in _events(lines[_OPS], lo, hi):
            busy.append((s, e))
            name = op_name(n)
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            key = f"{prog}/{name}"
            op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9
            for k in kernels:
                if name.startswith(k):
                    kernel_s[k] += (e - s) * 1e-9
        if not busy:
            continue
        used += 1
        merged = _merged(busy)
        busy_total += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            label = labels[bisect.bisect_right(cuts, 0.5 * (a + b))]
            gap_s[label] = gap_s.get(label, 0.0) + (b - a) * 1e-9
    window_s = (hi - lo) * 1e-9
    busy_s = busy_total * 1e-9 / used if used else 0.0
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_s, "window_s": window_s, "devices": used,
            "kernel_s": kernel_s, "module_s": module_s,
            "device_ops": rank(op_s), "idle_gaps": rank(gap_s)}
