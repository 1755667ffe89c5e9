"""Share of the HBM roofline of the GF(256) programs that a rebuild dispatches (jit_rs_matmul_batch_device: a stripe set's decode with the (k, k) decode matrix, and the re-encode of lost parity chunks): bytes in plus out, over their device time."""
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rebuild_MiBps"


def read(w):
    return w.roofline_pct("rs", ("rs_matmul_batch_device",))
