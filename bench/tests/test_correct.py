"""``correct`` holds for a sound run and fails for the control and faults.

Runs the harness end to end on the CPU (kernels in interpret mode) at a
size a test run can hold: two sessions of the cell's configuration, a short
window.  The chip check is skipped; everything after it runs as on the
chip.  The control, the reference with every matmul at three bfloat16
passes put in the program's place, has to fail the cell's limits, and so
has each fault of ``bench/faults.py`` planted in the program: the carry
left unchanged, half of each session's chains left out of its summary, and
one answer altered.
"""

import pytest

from bench import faults, harness

CELL = "clf_icu_pod16"


def _small():
    """The benchmark's cell at two sessions."""
    cell = harness.load_cell(CELL)
    cell.traffic = dict(cell.traffic, sessions=2, max_sessions=2,
                        warmup_s=0.3)
    return cell


def _correct(seed=2**31 + 17, controls=()):
    run, numbers = harness.run(CELL, seed, 2.0, False, require_chip=False,
                               cell=_small(), controls=controls)
    return harness.is_correct(run, numbers), numbers


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_sound_run_is_correct_and_control_is_not():
    ok, numbers = _correct(controls=("high",))
    assert ok, numbers
    limits = harness.limits(_small())
    failed = [k for k in limits
              if numbers.get(f"control:high:{k}", 0) > limits[k]]
    assert failed, numbers


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_caught(fault):
    with faults.FAULTS[fault]():
        ok, numbers = _correct()
    assert not ok, numbers
