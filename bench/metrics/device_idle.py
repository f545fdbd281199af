"""Share of the traced window in which no operation ran on the chip, in
percent: 1 - busy / window from the device trace.

One reader serves every cell; the metric takes the name
``device_idle.<kind>`` per end-to-end metric it moves, and the harness
reads ``device_idle.<kind>`` with this file."""


def read(obs):
    if obs.trace is None or not obs.trace["devices"] \
            or obs.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - obs.trace["busy_s"] / obs.trace["window_s"])
