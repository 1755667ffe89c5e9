"""User MiB whose write was acknowledged by the deadline (its whole stripe, parity included, persisted), over the window's wall seconds."""
UNIT = "MiB/s"
SOURCE = "host_clock"


def read(w):
    return w.mib("write") / w.window_s if w.mib("write") > 0 else None
