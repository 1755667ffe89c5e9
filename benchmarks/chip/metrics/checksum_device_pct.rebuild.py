"""Share of the rows that crc32c_many checksummed in the rebuild window (the blocks written to the replaced drives) that took the device path (its checksum:crc32c spans keyed by op crc32c_device or crc32c_host and shapes ((N, L),)); nothing without a device trace: off a chip the host path is the default."""
LAYER = "checksum"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "rebuild_MiBps"


def read(w):
    if w.program is None or w.device_events() is None:
        return None
    rows = {"crc32c_device": 0, "crc32c_host": 0}
    for (op, shapes), n in w.program["dispatches"].items():
        if op in rows:
            rows[op] += n * shapes[0][0]
    total = sum(rows.values())
    return 100.0 * rows["crc32c_device"] / total if total else None
