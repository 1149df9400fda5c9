"""A whole run of the harness on the CPU at a small size, past its look
for a chip: sound, ``correct`` is true; and the int8 control that
``bench/control.py`` reads on the chip fails the limit there."""
import pytest

pytest.importorskip("jax")

from _tiny import run                                   # noqa: E402


def test_sound_run_is_correct():
    result, _ = run()
    assert result["correct"], result["compared"]
    assert result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert {"setup_s", "output_tok_s"} <= set(result["metrics"])


def test_int8_control_fails_the_limit_the_program_passes():
    """The control of ``bench/control.py`` at a size a test run holds:
    put in the program's place, the tokens the int8 network puts first
    lie further below the float32 reference's best than the limits
    allow, so the run is not correct; the program's own tokens on the
    same sample pass them."""
    result, _ = run(control=True)
    assert not result["correct"]
    for name, c in result["compared"].items():
        if name in result["program_gap_numbers"]:
            assert result["program_gap_numbers"][name] <= c["limit"] < c["value"]
        else:
            assert c["value"] <= c["limit"]
