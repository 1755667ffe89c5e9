"""Share of the traced window in which no operation ran on the chip while checkpoints were saved: 1 - (union of device op intervals / window)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "write_MiBps"


def read(w):
    return w.idle_pct()
