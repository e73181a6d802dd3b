"""The copied trace generators against the program's own."""
import numpy as np
import pytest

from harness import tracegen


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
@pytest.mark.parametrize("app", sorted(tracegen.GENERATORS))
def test_generators_equal_the_programs(app, seed):
    from repro.traces import kernels

    n, foot = 1000, 1 << 20
    delta, is_write, dep, footprint = tracegen.GENERATORS[app](n, foot, seed)
    tr = kernels.KERNELS[app](n, foot, seed)
    assert footprint == int(tr.footprint_lines)
    assert int(tr.length) == len(delta) == n
    for mine, theirs in ((delta, tr.delta), (is_write, tr.is_write),
                         (dep, tr.dep)):
        theirs = np.asarray(theirs)
        assert mine.dtype == np.int32
        np.testing.assert_array_equal(mine, theirs[:n])
        assert not theirs[n:].any()


def test_unknown_app_fails():
    with pytest.raises(ValueError, match="unknown application"):
        tracegen.make_apps(["stream", "nonesuch"], 64, 1 << 10, 0)
