"""Self time of the program's media:read spans (the drive model's reads of the block and of a degraded read's survivors) per user MiB read."""
import programspans

LAYER = "media"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "read", "media:read")
