"""Self time of every service:* span of the program (the event loop, arrivals, dispatches, completions and the pipeline's handlers; calls back into the client excluded) per user MiB read."""
LAYER = "service & pipeline"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return w.program_per_mib_ms("read", layer="service")
