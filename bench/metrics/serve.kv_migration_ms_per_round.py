"""KV migration time per round, in milliseconds: the program's
``transport.exchange`` spans (encode, pack, the exchange and decode of
the balancer's windows, which the round waits for) over the rounds."""


def read(obs):
    rounds = obs.counters.get("rounds", 0)
    if not rounds or not obs.spans:
        return None
    return obs.span_s("transport.exchange") / rounds * 1e3
