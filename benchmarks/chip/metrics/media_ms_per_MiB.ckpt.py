"""Self time of the program's media:* spans (the drive model: media:append, media:read and the TimedDrive booking, media:book) per MiB of state saved."""
LAYER = "media"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.program_per_mib_ms("write", layer="media")
