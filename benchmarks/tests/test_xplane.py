"""The reducer: interval arithmetic on made-up events, then the small trace
recorded on the v5e (``fixtures/``, by ``tools/record_fixture.py``)."""

import glob
import json
import os

import pytest

from conftest import BENCH
from harness import xplane


def test_union_and_self_times():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    rows = [("%while.1 = () while(x)", 0.0, 100.0),
            ("%fusion.2 = f32[] fusion(a)", 10.0, 30.0),
            ("%custom-call.3 = f32[] custom-call(a)", 50.0, 40.0)]
    own = dict(xplane.self_times(rows))
    assert own["%while.1 = () while(x)"] == 30.0
    assert own["%custom-call.3 = f32[] custom-call(a)"] == 40.0


def test_kinds():
    assert xplane.kind_of("%custom-call.3 = f32[8]{0} custom-call(%a), "
                          "custom_call_target=\"tpu_custom_call\"") == "custom_call"
    assert xplane.kind_of("%all-reduce.1 = f32[8]{0} all-reduce(%a)") == "collective"
    assert xplane.kind_of("%fusion.7 = bf16[1,4096]{1,0} fusion(%p)") == "xla"
    assert xplane.op_name("%fusion.7 = bf16[1,4096]{1,0} fusion(%p)") == ("fusion.7", "fusion")


def test_reduce_on_made_up_trace():
    trace = {"devices": {"/device:TPU:0": [
        ("%fusion.1 = f32[] fusion(a)", 0.0, 1e9),
        ("%custom-call.2 = f32[] custom-call(a)", 2e9, 1e9),
        ("%all-reduce.3 = f32[] all-reduce(a)", 3e9, 5e8)]},
        "host": [("python3/1", "$sched.py:1 step", 0.9e9, 1.2e9),
                 ("python3/1", "outer", 0.0, 4e9)],
        "span_ns": (0.0, 3.5e9)}
    red = xplane.reduce(trace)
    assert red["chips"] == 1 and red["window_s"] == 3.5
    assert red["busy_s"] == 2.5 and red["custom_call_s"] == 1.0
    assert red["collective_s"] == 0.5 and red["xla_s"] == 1.0
    assert red["idle_gaps"][0][0] == 1e9
    gaps = xplane.attribute_gaps(trace, red["idle_gaps"], 5)
    assert gaps[0] == ["python3:$sched.py:1 step", 1.0]
    assert xplane.top_ops(red, 2)[0][0] in ("fusion.1", "custom-call.2")


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    BENCH, "fixtures", "*.xplane.pb"))), ids=os.path.basename)
def test_reduce_on_the_recorded_trace(path):
    with open(path.replace(".xplane.pb", ".expected.json")) as f:
        want = json.load(f)
    red = xplane.reduce(xplane.load(path))
    assert red["chips"] == want["chips"]
    assert 0 < red["busy_s"] < red["window_s"] <= want["host_wall_s"] * 1.5
    assert red["custom_call_s"] > 0          # the Pallas kernel, 3 launches
    assert red["idle_gaps"] and red["idle_gaps"][0][0] >= 0.9 * want["sleeps_s"] * 1e9
    assert (red["collective_s"] > 0) == (want["chips"] > 1)
    assert abs(red["custom_call_s"] + red["collective_s"] + red["xla_s"]
               - sum(red["ops"].values())) < 1e-9
