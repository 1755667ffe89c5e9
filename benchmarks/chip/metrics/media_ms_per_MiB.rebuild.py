"""Self time of the program's media:* spans (the drive model: the survivors' reads, the replaced zones' writes and the TimedDrive booking) per MiB restored."""
LAYER = "media"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "rebuild_MiBps"


def read(w):
    return w.program_per_mib_ms("rebuild", layer="media")
