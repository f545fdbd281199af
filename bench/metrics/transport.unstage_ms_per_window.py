"""Transport unstaging time per relocation window, in milliseconds: the
program's ``transport.unstage`` spans (slicing the received blocks,
decode, the collection's ``decode_rows`` and the host's first wait on
the device) over the windows.  A program that opens no such span reads
nothing."""


def read(obs):
    windows = obs.counters.get("windows", 0)
    if not windows or not any(s["name"] == "transport.unstage"
                              for s in obs.spans):
        return None
    return obs.span_s("transport.unstage") / windows * 1e3
