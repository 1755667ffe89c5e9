"""Share of the traced rebuild window in which no operation ran on the chip: 1 - (union of device op intervals / window)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rebuild_MiBps"


def read(w):
    return w.idle_pct()
