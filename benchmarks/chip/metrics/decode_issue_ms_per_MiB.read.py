"""Self time of the program's codec:issue spans (host time issuing the decode programs, the eager stack and index ops included) per user MiB read."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return w.program_per_mib_ms("read", "codec:issue")
