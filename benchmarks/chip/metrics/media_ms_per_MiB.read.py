"""Self time of the program's media:read spans (the drive model's reads of the block and of a degraded read's survivors) per user MiB read."""
LAYER = "media"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return w.program_per_mib_ms("read", "media:read")
