"""The control (the reference computed in bfloat16, put in the program's
place) has to fail each cell's check, on every seed tried."""
import pytest

from bench import cell as bcell
from bench import control

SEEDS = (3, 2 ** 31 + 5, 987654321)


@pytest.mark.parametrize("name,queries", [("mb-mid-open", 24),
                                          ("snow-uniondp-closed", 2)])
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_check(name, queries, seed):
    c = bcell.load_cell(name)
    limits = c["config"]["check"]["limits"]
    got = control.reading(c, seed, queries, 10)
    assert set(got) <= set(limits)
    assert any(got[k] > limits[k] for k in got), (got, limits)
