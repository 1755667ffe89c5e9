"""99th percentile (nearest rank) of submit-to-completion wall time over every read completed in the window: host and chip time, no modelled drive time."""
UNIT = "ms"
SOURCE = "host_clock"


def read(w):
    return w.percentile_ms("read", 99)
