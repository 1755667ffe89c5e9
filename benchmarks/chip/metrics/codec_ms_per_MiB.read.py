"""Self time of the codec (StripeCodec's pack, _to_device, dispatch and materialize, the jitted ops' dispatch) per user MiB read."""
LAYER = "codec"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "read_MiBps"


def read(w):
    return w.per_mib_ms(w.layer_s("codec"), "read")
