"""Share of the HBM roofline of the stripe-group GF(256) Reed-Solomon encode programs (jit_rs_matmul_batch_device, jit_rs_matmul_batch): bytes in plus out, over their device time. No published peak of GF(256) operations: bandwidth alone bounds it."""
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "write_MiBps"


def read(w):
    return w.roofline_pct("rs", ("rs_matmul_batch_device", "rs_matmul_batch"))
