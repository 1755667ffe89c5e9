"""Self time of the program's codec:h2d and codec:d2h spans (the survivors' copy to the device and the decoded chunks' copy back) per user MiB read."""
import programspans

LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "read", "codec:h2d", "codec:d2h")
