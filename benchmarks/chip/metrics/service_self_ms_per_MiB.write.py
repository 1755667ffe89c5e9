"""Self time of every service:* span of the program (the event loop, arrivals, dispatches, completions and the pipeline's handlers; calls back into the client excluded) per user MiB written."""
import programspans

LAYER = "service & pipeline"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "write", layer="service")
