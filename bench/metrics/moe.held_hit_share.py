"""Share of the held experts that a step's tokens reach, in percent: the
program's ``moe.held_experts_hit`` counter (held experts with at least
one of a step run's real tokens, summed over step runs and expert
layers) over step runs x expert layers x held experts.  The rest are
expert weights a step reads for no token."""


def read(obs):
    hit = obs.counters.get("moe.held_experts_hit")
    calls = obs.counters.get("calls")
    if hit is None or not calls:
        return None
    cfg = obs.config
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * hit / (len(calls) * layers * cfg["n_routed_experts"])
