"""Self time of the program's codec:wait spans (StripeCodec.materialize blocked on the device's encode result, before the copy back) per MiB of state saved."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.program_per_mib_ms("write", "codec:wait")
