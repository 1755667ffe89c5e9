"""Self time of the program's codec:h2d and codec:d2h spans (the stripe groups' data copied to the device for the encode and their parity copied back) per MiB of state saved."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.program_per_mib_ms("write", "codec:h2d", "codec:d2h")
