"""Self time of the program's codec:h2d and codec:d2h spans (the survivors' copy to the device and the decoded chunks' copy back) per user MiB read."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return w.program_per_mib_ms("read", "codec:h2d", "codec:d2h")
