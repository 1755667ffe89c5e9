"""Stats.device_blocks_written / Stats.host_blocks_written over the window: parity, padding and GC moves per user block."""
LAYER = "array"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "write_MiBps"


def read(w):
    h = w.stat("host_blocks_written")
    return w.stat("device_blocks_written") / h if h > 0 else None
