"""Self time of the program's codec:wait spans (StripeCodec.materialize blocked on the device's encode result, before the copy back) per user MiB written."""
import programspans

LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "write", "codec:wait")
