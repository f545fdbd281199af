"""The decode step's share of its roofline, in percent.

Each run of the jitted step needs every parameter once and each live
sequence's cached keys and values up to its position
(``counts.qwen2_step_bytes``, bf16), summed over every run in the
window, over the chip's HBM bandwidth.  The time is the device time of the
``jit_serve_step`` program in the trace.  At these batch sizes the step
is bound by bandwidth: its FLOPs over the bf16 peak take a small part of
the byte time."""

from bench.counts import qwen2_step_bytes


def read(obs):
    if obs.trace is None:
        return None
    t = obs.trace["module_s"].get("jit_serve_step", 0.0)
    calls = obs.counters.get("calls")
    if t <= 0 or not calls:
        return None
    need = sum(qwen2_step_bytes(obs.config, keys) for keys in calls)
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / t
