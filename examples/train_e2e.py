"""End-to-end training driver.

Default: a fast CPU demonstration (reduced smollm config, 20 steps) with
ZapRAID checkpointing, a storage-lane failure at step 8, and a simulated
preemption + restore at step 14.

``--full`` trains the real smollm-135m (~135M params, the assignment's
~100M-scale model) for 200 steps -- sized for a real accelerator host.

Run: PYTHONPATH=src python examples/train_e2e.py
"""
import sys

sys.argv = [sys.argv[0]] + (
    ["--arch", "smollm-135m", "--steps", "20", "--ckpt-every", "5",
     "--fail-lane", "2", "--fail-at", "8", "--restart-at", "14",
     "--global-batch", "8", "--seq-len", "64"]
    if "--full" not in sys.argv
    else ["--arch", "smollm-135m", "--no-smoke", "--steps", "200", "--ckpt-every", "25",
          "--global-batch", "32", "--seq-len", "2048"]
)
if "--full" in sys.argv:
    sys.argv.remove("--full")

from repro.launch import train

train.run(sys.argv[1:])
