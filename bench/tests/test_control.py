"""The control: the plain reference computed in bfloat16, the precision
below the float32 the configurations state, put in the program's place,
must come out not correct against the float32 reference, in every cell."""

import pytest

from bench.harness import compare, spec
from bench.harness import trace as TR

from small import small_cell


@pytest.mark.parametrize("name", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_control_fails(name):
    cell = small_cell(name)
    drv = spec.load_driver(cell.mix["driver"])(cell.config, cell.mix,
                                               2**31 + 9, TR.Spans())
    drv.setup()
    drv.window(0.3)
    got = drv.outputs()
    drv.release()
    want = drv.reference("float32")
    ok, _ = compare.judge(drv.numbers(got, want), cell.config["limits"])
    assert ok
    ctrl = drv.as_outputs(drv.reference("bfloat16"), "bfloat16")
    ok, checks = compare.judge(drv.numbers(ctrl, want), cell.config["limits"])
    assert not ok, checks
