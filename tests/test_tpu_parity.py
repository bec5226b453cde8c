"""The on-device parity sweep (hack/tpu_parity_smoke.py), here on CPU.

On the chip the sweep runs in-process as the first phase of
`python chip_smoke.py`, with `require_tpu=True`. These tests run the same
sweep on the suite's CPU backend and check that the chip form refuses any
other backend.
"""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "hack")
)

import tpu_parity_smoke  # noqa: E402


def test_parity_sweep_on_cpu_backend():
    verdict = tpu_parity_smoke.run()
    assert verdict["parity"] == "ok"
    assert verdict["cases_checked"] > 0


def test_parity_sweep_refuses_non_tpu_when_required():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        tpu_parity_smoke.run(require_tpu=True)
