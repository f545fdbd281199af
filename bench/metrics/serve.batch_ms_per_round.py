"""Batch assembly time per serving round, in milliseconds: the
program's ``serve.stack`` spans (stacking the resident KV slices into
padded batches) and ``serve.unstack`` spans (writing each decoded slice
back into its sequence) over the rounds.  A program that opens no
``serve.stack`` span reads nothing."""


def read(obs):
    rounds = obs.counters.get("rounds", 0)
    if not rounds or not any(s["name"] == "serve.stack" for s in obs.spans):
        return None
    own = obs.span_s("serve.stack") + obs.span_s("serve.unstack")
    return own / rounds * 1e3
