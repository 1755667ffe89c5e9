"""Time in crc32c_many (as core/array.py and core/zns.py imported it) per user MiB written."""
LAYER = "checksum"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.per_mib_ms(w.layer_s("checksum"), "write")
