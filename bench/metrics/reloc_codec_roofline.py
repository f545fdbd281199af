"""The relocation codec kernels' share of their roofline, in percent.

The work is counted from the payload, whatever implements it: every
relocated record is read and written once by encode+pack and once by
decode (``counts.codec_bytes``), over the chip's HBM bandwidth.  The
time is the device time of the ``reloc_pack_rows`` and
``reloc_decode_rows`` kernels in the trace.  The kernels are bound by
bandwidth; they do no arithmetic to speak of."""

from bench.counts import codec_bytes


def read(obs):
    if obs.trace is None:
        return None
    t = sum(obs.trace["kernel_s"].get(k, 0.0)
            for k in ("reloc_pack_rows", "reloc_decode_rows"))
    rows = obs.counters.get("rows", 0)
    if t <= 0 or not rows:
        return None
    need = codec_bytes(rows, obs.config["record_bytes"])
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / t
