"""A sound run of each kind of cell comes out correct under the cell's
own limits, and the control (the reference in float8) does not."""
import pytest

from bench import compare, reference, spec
from bench_tiny import run_tiny, tiny_cell


@pytest.mark.parametrize("name", ["stablelm-12b.sketch-s2048",
                                  "granite-34b.plain-s8192"])
def test_sound_run_is_correct(name):
    out = run_tiny(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert {m for m in out["metrics"]} == {
        m["name"] for m in spec.cell(name).end_to_end}
    assert out["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("name", ["stablelm-12b.sketch-s2048",
                                  "granite-34b.plain-s8192"])
def test_control_is_not_correct(name):
    c = tiny_cell(name)
    rs = spec.reference_spec(c)
    seed = 2 ** 31 + 5
    ref = reference.run(rs, seed)
    ctl = reference.run(rs, seed, precision="fp8")
    ok, checks = compare.judge(
        compare.cell_readings(c.traffic, ctl, ref), c.limits)
    assert not ok, checks
