"""Self time of the program's checksum:crc32c spans (every integrity.checksum.crc32c_many call) per MiB of state saved."""
LAYER = "checksum"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "write_MiBps"


def read(w):
    return w.program_per_mib_ms("write", "checksum:crc32c")
