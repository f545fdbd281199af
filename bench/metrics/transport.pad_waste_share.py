"""Share of the transport's wire bytes that are row-class padding, in
percent: ``pad_waste_bytes / wire_bytes`` summed over the window's
relocation windows, from each window's ``TransportStats``."""


def read(obs):
    wire = obs.counters.get("wire_bytes", 0)
    if not wire:
        return None
    return 100.0 * obs.counters["pad_waste_bytes"] / wire
