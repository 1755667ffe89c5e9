"""Self time of ZapRAIDArray.write/read/flush/maybe_gc, less the codec and checksum calls inside them, per user MiB written."""
LAYER = "array"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.per_mib_ms(w.layer_s("array"), "write")
