"""The control comes out not correct: at the positions the program served,
the fp8 reference's first choices lie further below the float32
reference's best than the cell's ``gap_limit`` allows, while the program's
own served tokens stay inside it.  At smoke widths on the CPU; the
readings at the cells' own sizes, on the chip, are in PERF.md."""
import jax
import pytest

from bench import control, spec


@pytest.mark.parametrize("cell", ["stablelm-1.6b.chat"])
def test_control_fails_the_limit(cell):
    c = spec.load_cell(cell)
    r = control.readings(c, [2**32 + 11], 6.0, jax.devices()[:1], smoke=True)
    (_, prog, ctrl), = r
    limit = c.geometry["gap_limit"]
    assert prog <= limit < ctrl
