"""A whole run of the harness on the CPU at a small size, with the timed
path broken underneath: ``correct`` comes out false, once for each fault
a served cell can have."""
import pytest

pytest.importorskip("jax")

from _tiny import run                                   # noqa: E402
from bench.core import faults                           # noqa: E402


@pytest.mark.parametrize("fault", list(faults.ALL.values()),
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(fault):
    result, calls = run(fault)
    assert calls, "the fault was never reached"
    assert not result["correct"], result["compared"]


