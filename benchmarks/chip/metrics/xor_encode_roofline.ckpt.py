"""Share of the HBM roofline (819 GB/s on v5e) of the stripe-group XOR encode programs (jit_xor_parity_batch_device, jit_xor_parity_batch) while checkpoints were saved: bytes in plus out, over their device time in the trace, as xor_encode_roofline reads them."""
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "write_MiBps"


def read(w):
    return w.roofline_pct("xor", ("xor_parity_batch_device", "xor_parity_batch"))
