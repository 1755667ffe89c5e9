"""Self time of the program's ckpt:pack spans (a save's leaf bytes laid into padded 4 KiB blocks and their extents allocated, CheckpointEngine._stage_save) per MiB of state saved."""
LAYER = "checkpoint"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.program_per_mib_ms("write", "ckpt:pack")
