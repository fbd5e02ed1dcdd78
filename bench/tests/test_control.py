"""The control must come out not correct: the plain reference put in the
program's place one precision step below the configuration's
(``bench/readings.py`` reads it at the cells' own sizes on the chip;
here at a size a CPU holds)."""
import jax
import pytest

from bench.readings import read_seed
from bench.tests.small import cell


@pytest.mark.parametrize("name", ["v100.campaign", "qwen05.saturated"])
def test_control_is_not_correct(name):
    c = cell(name)
    # a window of no length runs one answer or one batch: the same
    # requests on every machine
    row = read_seed(c, 2 ** 31 + 17, 0.0, jax.devices()[:1])
    lim = c["limits"]
    sound = {k: row[k] for k in lim}
    control = {k: row[f"control.{k}"] for k in lim}
    assert all(sound[k] <= lim[k] for k in lim), sound
    assert any(control[k] > lim[k] for k in lim), control
