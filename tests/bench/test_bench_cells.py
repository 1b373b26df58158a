"""Every cell's set-up, window and check, called as functions at a
small size on the CPU: the measured numbers are there, and a sound
program comes out correct."""
import pytest

from bench_small import SMALL, run_small


@pytest.mark.parametrize("name", sorted(SMALL))
def test_cell_runs_and_is_correct(name, tmp_path):
    out, checks, correct = run_small(name, 2 ** 31 + 77, str(tmp_path))
    assert correct, [c for c in checks if not c.ok]
    assert out.attempted >= 1 and out.failed == 0
    (metric, value), = out.metrics.items()
    assert value > 0, metric
    assert {c.name for c in checks}          # each check has a name
