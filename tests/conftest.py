import os
import sys

import pytest

# src layout import without install; tests must see ONE cpu device (the
# 512-device forcing lives only inside launch/dryrun.py subprocesses).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def on_tpu(monkeypatch):
    """The models' kernel dispatch as on a TPU (``ops.on_tpu``), the
    kernels themselves run in interpret mode on the CPU.  From the test's
    start, and after ``on_tpu(True)``; ``on_tpu(False)`` takes the CPU's
    paths again."""
    from repro.kernels import ops
    tpu = [True]
    monkeypatch.setattr(ops, "on_tpu", lambda: tpu[0])

    def set_tpu(flag: bool) -> None:
        tpu[0] = flag
    return set_tpu
