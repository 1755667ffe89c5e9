"""Device programs per decode: the module events (XLA Modules line) that start inside the traced window, over the program's codec:issue spans in the window (a read cell issues decodes only)."""
import tracereduce

LAYER = "codec"
UNIT = "programs"
SOURCE = "device_trace"
MOVES = "read_MiBps"


def read(w):
    events = w.device_events()
    issued = w.program_count("codec:issue")
    if events is None or not issued:
        return None
    t0, t1 = w.trace["t0_ns"], w.trace["t1_ns"]
    modules = sum(1 for line, _, start, _ in events
                  if line == tracereduce.MODULES_LINE and t0 <= start <= t1)
    return modules / issued
