"""Self time of the program's checksum:crc32c spans (the CRC32C of every block written to a replaced drive) per MiB restored."""
LAYER = "checksum"
UNIT = "ms/MiB"
SOURCE = "program_span"
MOVES = "rebuild_MiBps"


def read(w):
    return w.program_per_mib_ms("rebuild", "checksum:crc32c")
