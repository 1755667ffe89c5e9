"""Seconds from process start to the window's first request: JAX and TPU start-up, building the array, the prefill, the drive failures and the warm-up (compile, or loading the compile cache)."""
UNIT = "s"
SOURCE = "host_clock"


def read(w):
    return w.setup_s
