"""Self time of the program's codec:wait spans (StripeCodec.materialize blocked on the device's decode result, before the copy back) per user MiB read."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return w.program_per_mib_ms("read", "codec:wait")
