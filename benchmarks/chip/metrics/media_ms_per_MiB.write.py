"""Self time of the program's media:* spans (the drive model: media:append, media:read and the TimedDrive booking, media:book) per user MiB written."""
import programspans

LAYER = "media"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "write", layer="media")
