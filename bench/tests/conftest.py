import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def cpu_harness(monkeypatch):
    """Let the harness run on the CPU: no refusal for want of a TPU, and
    the v5e's peaks for any device."""
    from bench import run as R

    monkeypatch.setattr(R, "require_chips", lambda devices, chips: None)
    monkeypatch.setattr(R, "peaks_for",
                        lambda kind: R.load_json("peaks.json")["devices"]
                        ["TPU v5 lite"])
