"""The control and the planted faults (``bench/control.py``), kept at a
size a test run can hold.  On the chip at the cells' own sizes the float32
control fails the limits (PERF.md gives its readings); on the CPU float32
is more accurate than the TPU's (its ``exp`` above all), so here the
control is held to reading far worse than the sound program, and each
fault planted in the reference to coming out not correct under the cell's
own limits."""
import pytest

from bench import compare, control
from bench.tests import _tiny


@pytest.mark.parametrize("name", ["flight-exact", "flight-svi-stream",
                                  "usps-gplvm-exact"])
def test_control_and_faults(name):
    c = _tiny.cell(name)
    r = control.readings_for_seed(c, 2**31 + 99, True)

    def correct(kind):
        return compare.judge(r[kind], c["limits"])[0]

    assert correct("program"), r["program"]
    assert max(r["control_f32"][k] / max(r["program"][k], 1e-300)
               for k in compare.NAMES) > 5.0, (r["control_f32"], r["program"])
    for kind in ("unchanged", "half_batch", "altered"):
        assert not correct(kind), (kind, r[kind])
