"""Serving driver time per round outside the decode step and the KV
exchange, in milliseconds: the round's wall time (the benchmark's clock
around ``decode_round`` and the admissions that replace completions)
less the program's ``serve.decode_batch`` and ``transport.exchange``
spans.  It covers stacking and unstacking the batch, admission and its
host-to-device copy, and the balancer's planning and extraction."""


def read(obs):
    rounds = obs.counters.get("rounds", 0)
    if not rounds or not obs.spans:
        return None
    own = (obs.counters["round_s"] - obs.span_s("serve.decode_batch")
           - obs.span_s("transport.exchange"))
    return own / rounds * 1e3
