"""Self time of the program's array:stage spans (ZapRAIDArray.write staging the request's blocks into the stripe arenas, less the group builds and commits it sets off) per user MiB written."""
import programspans

LAYER = "array"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "write", "array:stage")
