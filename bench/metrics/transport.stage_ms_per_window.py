"""Transport staging time per relocation window, in milliseconds: the
program's ``transport.stage`` spans (each payload's encode, the slot
tables and the arena, up to the pack call) over the windows.  A program
that opens no such span reads nothing."""


def read(obs):
    windows = obs.counters.get("windows", 0)
    if not windows or not any(s["name"] == "transport.stage"
                              for s in obs.spans):
        return None
    return obs.span_s("transport.stage") / windows * 1e3
