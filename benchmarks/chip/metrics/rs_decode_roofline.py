"""Share of the HBM roofline of the single-stripe GF(256) program (jit_rs_matmul) that a degraded 4 KiB read dispatches: bytes in plus out, over its device time."""
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "read_MiBps"


def read(w):
    return w.roofline_pct("rs", ("rs_matmul",))
