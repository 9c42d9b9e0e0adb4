"""The comparison that decides ``correct`` fails where it must: with the
4-bit reference in the program's place (the precision control) and with
each planted fault, each caught by the number it is planted for."""

import pytest

from fenixbench import control, run

from .conftest import TINY, bench_root

CELLS = ["cnn_iscx", "rnn_iscx"]
SEED = 2**31 + 21
# the compared number each planted kind must push over its limit
CAUGHT_BY = {"control": "verdict_gap", "altered": "verdict_gap",
             "half_lanes": "lanes_lost", "half_answers": "answers_lost",
             "half_delivery": "due_missed_pct",
             "gate_half": "admission_gap_pct"}


def _measure(cell, tmp_path):
    return run.measure(cell, SEED, 0.6, False, require_tpu=False,
                       control=True, overrides=TINY,
                       root=bench_root(tmp_path))


def test_every_kind_is_caught():
    assert set(CAUGHT_BY) == set(control.KINDS)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_reads_zero_and_control_gap_is_positive(cell,
                                                         tmp_path):
    m = _measure(cell, tmp_path)
    assert m["result"]["correct"] is True
    assert m["check"]["verdict_gap"] == 0
    assert m["check"]["lanes_lost"] == m["check"]["answers_lost"] == 0
    assert m["info"]["control_gap"] > 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", sorted(CAUGHT_BY))
def test_control_and_faults_are_not_correct(cell, kind, tmp_path):
    with control.serving(kind):
        m = _measure(cell, tmp_path)
    assert m["result"]["correct"] is False
    name = CAUGHT_BY[kind]
    assert m["check"][name] > m["result"]["check"][name]["limit"]
