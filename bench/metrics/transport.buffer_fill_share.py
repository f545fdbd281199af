"""Share of the dense send buffers that carries payload, in percent:
the payload bytes (``row_bytes``) over the bytes of the send buffers
the exchanges allocated (``buffer_bytes``), summed over the window's
``transport.exchange`` spans, which carry both.  A program whose spans
lack ``buffer_bytes`` reads nothing."""


def read(obs):
    rows = buf = 0
    for s in obs.spans:
        args = s.get("args") or {}
        if s["name"] == "transport.exchange" and "buffer_bytes" in args:
            rows += args["row_bytes"]
            buf += args["buffer_bytes"]
    if not buf:
        return None
    return 100.0 * rows / buf
