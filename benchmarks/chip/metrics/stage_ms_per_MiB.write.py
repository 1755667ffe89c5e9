"""Self time of the program's array:stage spans (ZapRAIDArray.write staging the request's blocks into the stripe arenas, less the group builds and commits it sets off) per user MiB written."""
LAYER = "array"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.program_per_mib_ms("write", "array:stage")
