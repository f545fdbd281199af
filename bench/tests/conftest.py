"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test can hold.

The tests drive the same harness, drivers and references as a chip run,
at tiny widths, with the check for a chip switched off.  Nothing here is
a device number.
"""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_MODEL = dict(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, intermediate_size=128,
                  num_hidden_layers=2, vocab_size=300)


def tiny_cell(name: str) -> dict:
    """Cell ``name`` of ``BENCHMARK.json`` at tiny sizes."""
    from bench import harness

    c = harness.load_cell(name)
    if c["config"]["system"] == "distarray_reloc":
        c["config"] = dict(c["config"], rows_per_place=2048)
        c["mix"] = dict(c["mix"], block_records=64, moves_per_window=4,
                        head_bits=12, warmup_windows=4)
    else:
        serving = dict(c["config"]["serving"], s_cache=64, max_batch=4,
                       slots_per_replica=8)
        c["config"] = dict(c["config"], **TINY_MODEL, serving=serving,
                           correct_limits={"max_logit_gap": 0.01})
        c["mix"] = dict(c["mix"], population=8, warmup_rounds=8,
                        prompt={"median": 20, "sigma": 0.5},
                        output={"median": 6, "sigma": 0.5},
                        warm_migrations=[1, 2])
    return c


@pytest.fixture
def run_tiny():
    from bench import harness

    def run(name, seed=7, seconds=1.0, trace=False, control=False):
        return harness.run(name, seed, seconds, trace, require_tpu=False,
                           loaded=tiny_cell(name), control=control)

    return run
