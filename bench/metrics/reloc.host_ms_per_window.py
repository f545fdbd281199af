"""Relocation engine self time per window, in milliseconds: the
``reloc.phase1`` and ``reloc.commit`` spans of the program's telemetry
less the ``transport.exchange`` span that ``reloc.commit`` holds.  That
is the engine's extraction, insertion and accounting on the host."""


def read(obs):
    windows = obs.counters.get("windows", 0)
    if not windows or not obs.spans:
        return None
    own = (obs.span_s("reloc.phase1") + obs.span_s("reloc.commit")
           - obs.span_s("transport.exchange"))
    return own / windows * 1e3
