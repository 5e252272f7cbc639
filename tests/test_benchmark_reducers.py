"""The benchmark's trace reducers under tier-1.

``benchmarks/selfcheck.py`` holds every reducer of ``benchmarks/trace.py``
(busy and idle time, device ms a step, class shares, exposed collective
time, idle gaps, top op) to hand-worked answers on a hand-made list of
events and to the numbers pinned beside one step recorded on the chip.
It needs no model and no device, so it runs here; the harness's dry
runs, which build ResNet-50, stay by hand."""

import pytest

from benchmarks import selfcheck


def test_reducers_give_the_fixtures_answers(capsys):
    try:
        selfcheck.check_reducers()
    except SystemExit as miss:  # how selfcheck reports a wrong answer
        pytest.fail(str(miss))
    said = capsys.readouterr().out
    # both fixtures were read, and each held its reducers to answers
    assert "handmade_events.json busy_ns" in said
    assert "gpt2m_s1024_chip_events.json device_ms_per_step" in said
