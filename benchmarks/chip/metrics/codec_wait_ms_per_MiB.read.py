"""Self time of the program's codec:wait spans (StripeCodec.materialize blocked on the device's decode result, before the copy back) per user MiB read."""
import programspans

LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "read", "codec:wait")
