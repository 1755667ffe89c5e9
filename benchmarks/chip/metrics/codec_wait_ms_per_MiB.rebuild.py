"""Self time of the program's codec:wait spans (StripeCodec.materialize blocked on a rebuild's decode or re-encode, before the copy back) per MiB restored."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "rebuild_MiBps"


def read(w):
    return w.program_per_mib_ms("rebuild", "codec:wait")
