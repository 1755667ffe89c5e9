"""Self time of the program's array:bookkeep spans (a committed group's L2P, CST and validity updates, less the pipeline's completion handler inside) per user MiB written."""
import programspans

LAYER = "array"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "write", "array:bookkeep")
