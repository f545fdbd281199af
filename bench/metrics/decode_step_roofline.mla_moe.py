"""The latent-attention, sparse-expert decode step's share of its
roofline, in percent.

Each run of the jitted step needs every held parameter but the
embedding table and the routed experts once, the weights of each held
expert that a token of the run was routed to, and each live sequence's
cached latent rows up to its position (``counts_mla_moe.step_bytes``,
bf16), summed over every run in the window, over the chip's HBM
bandwidth.  The experts hit come from the program's
``moe.held_experts_hit`` counter over the window.  The time is the
device time of the ``jit_serve_step`` program in the trace.  At these
batch sizes the step is bound by bandwidth."""

from bench.counts_mla_moe import expert_bytes, step_bytes


def read(obs):
    if obs.trace is None:
        return None
    t = obs.trace["module_s"].get("jit_serve_step", 0.0)
    calls = obs.counters.get("calls")
    hit = obs.counters.get("moe.held_experts_hit")
    if t <= 0 or not calls or hit is None:
        return None
    need = sum(step_bytes(obs.config, keys, 0) for keys in calls) \
        + hit * expert_bytes(obs.config)
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / t
