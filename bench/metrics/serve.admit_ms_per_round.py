"""Admission time per serving round, in milliseconds: the program's
``serve.admit`` spans (placement, the new sequence's KV and its copy to
the device) over the rounds.  A round admits only what completed, so
some rounds admit nothing; a program that opens no ``serve.stack`` span
(and so predates the admission span too) reads nothing."""


def read(obs):
    rounds = obs.counters.get("rounds", 0)
    if not rounds or not any(s["name"] == "serve.stack" for s in obs.spans):
        return None
    return obs.span_s("serve.admit") / rounds * 1e3
