"""Rehearsal of ``chip_smoke.py`` on the CPU.

The script runs only on a TPU, so its phases would otherwise rot between
chip runs.  Here they run at a tiny row count with the Pallas kernels
interpreted; only the script's device requirement is left out (and checked
on its own: it must refuse the CPU before any phase runs).
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402


def test_chip_smoke_refuses_a_machine_without_a_tpu():
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as e:
        chip_smoke.require_tpu(1)
    assert "no TPU found" in str(e.value)
    assert repr(jax.devices()[0].platform) in str(e.value)


def test_chip_smoke_single_chip_phases_on_cpu(capsys):
    chip_smoke.run_single_chip(m=3_000, seed=0,
                               read=chip_smoke.Reading("cpu"))
    out = capsys.readouterr().out
    for phase in ("data", "udt", "kernels", "toot", "boosting", "serving"):
        assert f"[{phase}]" in out, phase
