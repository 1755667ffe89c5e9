"""Self time of the program's codec:h2d and codec:d2h spans (the survivors' copy to the device and the decoded or re-encoded chunks' copy back) per MiB restored."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "rebuild_MiBps"


def read(w):
    return w.program_per_mib_ms("rebuild", "codec:h2d", "codec:d2h")
