"""Self time of the program's checksum:crc32c spans (every integrity.checksum.crc32c_many call) per user MiB written."""
import programspans

LAYER = "checksum"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return programspans.per_mib_ms(w, "write", "checksum:crc32c")
