"""MiB that the replaced drives hold after each pass (the sum of their zones' write pointers times the block size), over the window's wall seconds."""
UNIT = "MiB/s"
SOURCE = "host_clock"


def read(w):
    return w.mib("rebuild") / w.window_s if w.mib("rebuild") > 0 else None
