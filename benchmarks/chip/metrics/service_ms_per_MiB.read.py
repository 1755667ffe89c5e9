"""Window wall milliseconds outside every wrapped layer (service, handler pipeline, event loop) per user MiB read."""
LAYER = "service & pipeline"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return w.per_mib_ms(w.service_s(), "read")
