"""Self time of the codec (StripeCodec's pack, _to_device, dispatch and materialize, the metadata parity helpers, the jitted ops' dispatch) per user MiB written."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.per_mib_ms(w.layer_s("codec"), "write")
