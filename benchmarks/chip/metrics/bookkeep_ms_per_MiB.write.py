"""Self time of the program's array:bookkeep spans (a committed group's L2P, CST and validity updates, less the pipeline's completion handler inside) per user MiB written."""
LAYER = "array"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.program_per_mib_ms("write", "array:bookkeep")
