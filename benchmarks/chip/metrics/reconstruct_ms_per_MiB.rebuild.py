"""Self time of the program's array:reconstruct spans (the batched whole-zone reconstruction: the per-chunk survivor walk, the gathers' assembly and the decoded chunks' placement, less the codec, media and checksum spans inside) per MiB restored."""
LAYER = "array"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "rebuild_MiBps"


def read(w):
    return w.program_per_mib_ms("rebuild", "array:reconstruct")
