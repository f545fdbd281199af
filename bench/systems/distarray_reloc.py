"""Relocation windows over a ``DistArray``: ``CollectiveMoveManager``
with the configuration's transport, window after window.

Each block of the traffic's key space is a chunk of its own from the
start.  A window registers the traffic's block moves as range moves,
runs ``sync()`` and reconciles the tracked distribution
(``update_dist``); it ends when its entries are committed and readable
at their destinations.  The window loop is closed: the next window
starts when the last one ends.
"""
from __future__ import annotations

import time
import traceback

import numpy as np

from .. import generators
from ..reference import relocation as ref

KERNELS = ("reloc_pack_rows", "reloc_decode_rows")


def record_data(seed: int, rows: int, record_bytes: int,
                dtype: str) -> np.ndarray:
    """``rows`` records of ``record_bytes`` random bytes each, viewed as
    ``dtype`` words: the relocation cells' data, the same for a seed."""
    dt = np.dtype(dtype)
    if record_bytes % dt.itemsize:
        raise ValueError(f"{record_bytes}-byte records are not whole "
                         f"{dt} words")
    raw = np.random.default_rng(seed).bytes(rows * record_bytes)
    return np.frombuffer(raw, dt).reshape(rows, record_bytes // dt.itemsize)


def _data(config: dict, seed: int) -> np.ndarray:
    return record_data(seed, int(config["places"])
                       * int(config["rows_per_place"]),
                       config["record_bytes"], config["record_dtype"])


class System:
    def __init__(self, config: dict, mix: dict, seed: int):
        from repro.core import (CollectiveMoveManager, DistArray, LongRange,
                                PlaceGroup, make_transport)

        self.config, self.mix, self.seed = config, mix, seed
        self.places = int(config["places"])
        self.traffic = generators.load(mix, config, seed)
        data = _data(config, seed)
        self.group = PlaceGroup(self.places)
        self.col = DistArray(self.group, track=True)
        for b, p in enumerate(self.traffic.owner):
            start, end = self.traffic.block_range(b)
            self.col.add_chunk(int(p), LongRange(start, end),
                               data[start:end].copy())
        self.mm = CollectiveMoveManager(
            self.group, transport=make_transport(config["transport"]))
        self.windows = 0
        self.wire_rows = 0
        self.failed = 0

    def _window(self, moves) -> None:
        import jax
        from repro.core import LongRange

        for start, end, _, dest in moves:
            self.col.move_range_at_sync(LongRange(start, end), dest, self.mm)
        with jax.profiler.TraceAnnotation("bench.sync"):
            self.mm.sync()
        with jax.profiler.TraceAnnotation("bench.update_dist"):
            self.col.update_dist()
        self.windows += 1
        self.wire_rows += self.mm.last_transport_stats.rows

    def warm(self) -> None:
        """Every send-buffer size the windows can need, then windows of
        the mix until the balance settles, before the clock starts."""
        for moves in self.traffic.warmup():
            self._window(moves)

    def run_window(self, seconds: float) -> dict:
        import jax

        record = int(self.config["record_bytes"])
        times, rows = [], 0
        wire = waste = failed = 0
        t_start = time.perf_counter()
        while True:
            moves = self.traffic.next_window()
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    self._window(moves)
            except Exception:  # noqa: BLE001 - a failed window is counted
                if not failed:
                    traceback.print_exc()
                failed += 1
                self.failed += 1
                self.windows += 1
            else:
                st = self.mm.last_transport_stats
                rows += st.rows
                wire += st.wire_bytes
                waste += st.pad_waste_bytes
            t1 = time.perf_counter()
            times.append(t1 - t0)
            if t1 - t_start >= seconds:
                break
        elapsed = t1 - t_start
        return {
            "metrics": {
                "relocated_GB_per_s": rows * record / elapsed / 1e9,
                "window_p95_ms": float(np.percentile(times, 95)) * 1e3,
            },
            "attempted": len(times), "failed": failed,
            "counters": {"windows": len(times), "rows": rows,
                         "payload_bytes": rows * record,
                         "wire_bytes": wire, "pad_waste_bytes": waste,
                         "window_median_ms": float(np.median(times)) * 1e3},
        }

    def finish(self) -> dict:
        holdings = {p: self.col.to_local_matrix(p)
                    for p in range(self.places)}
        owners = [(r.start, r.end, o)
                  for r, o in self.col.get_distribution().items()]
        return {"holdings": holdings, "owners": owners,
                "windows": self.windows, "wire_rows": self.wire_rows,
                "failed": self.failed}


def check(config: dict, mix: dict, seed: int, outcome: dict, *,
          control: bool = False) -> dict:
    """``{name: (value, limit)}`` for the program's run, or with
    ``control`` for the reference put in its place one precision down:
    the model's holdings with every record rounded to bfloat16."""
    data = _data(config, seed)
    owner, moved = ref.model_owner(generators.load(mix, config, seed),
                                   outcome["windows"])
    if control:
        holdings = ref.model_holdings(owner, ref.bf16_rows(data))
        owners = ref.owner_ranges(owner)
        checks = ref.compare(holdings, owners, data, owner, moved, moved)
        checks["failed_windows"] = (0, 0)
        return checks
    checks = ref.compare(outcome["holdings"], outcome["owners"], data,
                         owner, moved, outcome["wire_rows"])
    checks["failed_windows"] = (outcome["failed"], 0)
    return checks
