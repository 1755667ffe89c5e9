"""Make ``src/`` and this directory importable regardless of invocation cwd.

Keeps the tier-1 command (``PYTHONPATH=src python -m pytest``) working while
also letting a bare ``pytest`` run find both ``repro`` and the ``_hyp``
hypothesis shim.
"""
import sys
from pathlib import Path

import pytest

_HERE = Path(__file__).resolve().parent
for p in (str(_HERE), str(_HERE.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def host_spans():
    """A wall-clock host-span recorder (``repro.obs.HostSpans``) installed
    for one test and removed after it: the recorder is per process, and
    nothing may leak into the next test on the same worker."""
    from repro.obs import HostSpans

    rec = HostSpans().install()
    try:
        yield rec
    finally:
        rec.uninstall()
