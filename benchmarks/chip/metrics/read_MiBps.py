"""User MiB returned by the deadline, over the window's wall seconds."""
UNIT = "MiB/s"
SOURCE = "host_clock"


def read(w):
    return w.mib("read") / w.window_s if w.mib("read") > 0 else None
