"""Share of the rows the pack kernel gathered that start on a chunk
boundary, in percent: ``rows_aligned`` over ``rows_aligned +
rows_rotated``, summed over the window's ``codec.pack`` spans: how much
of the staging arena's layout already suits a plain copy (an aligned
row's rotate moves no lane).  A program that opens no such span reads
nothing."""


def read(obs):
    aligned = rotated = 0
    for s in obs.spans:
        args = s.get("args") or {}
        if s["name"] == "codec.pack" and "rows_aligned" in args:
            aligned += args["rows_aligned"]
            rotated += args["rows_rotated"]
    if not aligned + rotated:
        return None
    return 100.0 * aligned / (aligned + rotated)
