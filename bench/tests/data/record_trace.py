#!/usr/bin/env python3
"""Record ``codec_small.xplane.pb`` on a chip: three relocation windows
of the ``reloc_ycsb_zipf`` mix over a smaller ``DistArray``, each
followed by a host sleep of 10 ms, inside the ``bench.traced`` span.

    python bench/tests/data/record_trace.py bench/tests/data/codec_small.xplane.pb
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> None:
    import jax

    from bench import harness, trace_reduce
    from bench.systems import distarray_reloc as dr

    c = harness.load_cell("reloc_ycsb_zipf")
    system = dr.System(dict(c["config"], rows_per_place=16384), c["mix"], 1)
    system.warm()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir, profiler_options=harness._trace_options())
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.window"):
                system._window(system.traffic.next_window())
            with jax.profiler.TraceAnnotation("bench.host_sleep"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    shutil.copy(sorted(Path(tdir).rglob("*.xplane.pb"))[-1], out)
    shutil.rmtree(tdir)


if __name__ == "__main__":
    main(sys.argv[1])
