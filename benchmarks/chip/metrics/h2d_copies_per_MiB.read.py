"""Host-to-device copies the codec made (Stats.h2d_copies) over the window, per user MiB read."""
LAYER = "codec"
UNIT = "copies/MiB"
SOURCE = "program_counter"
MOVES = "read_MiBps"


def read(w):
    mib = w.mib("read")
    return w.stat("h2d_copies") / mib if mib > 0 else None
