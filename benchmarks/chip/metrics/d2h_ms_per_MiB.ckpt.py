"""Self time of the program's ckpt:d2h spans (a save's leaves copied out of the device's memory into host arrays, CheckpointEngine._stage_save) per MiB of state saved."""
LAYER = "checkpoint"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.program_per_mib_ms("write", "ckpt:d2h")
