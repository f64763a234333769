"""The trace reduction on events recorded on one TPU v5 lite
(fixtures/tpu_trace_events.json), against numbers worked out by hand."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import trace  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_trace_events.json"


@pytest.fixture(scope="module")
def events():
    return json.loads(FIXTURE.read_text())


def test_instruction_and_output_dims(events):
    ops = events["devices"][0]["ops"]
    names = [trace.instruction(o["name"]) for o in ops]
    assert names == ["copy-start", "fusion.48", "reshape.156", "copy-done",
                     "fusion.13", "adc_quantize_pallas_population.1",
                     "while.2"]
    assert trace.output_dims(ops[5]["name"]) == (32, 1488, 21)
    assert trace.output_dims(ops[1]["name"]) == (32, 21, 16, 1)


def test_busy_is_the_union_of_op_intervals(events):
    # [299,305) 6 + [307,596) 289 (fusion.48 and reshape.156 touch at 379)
    # + [598,1283) 685 + [1284,108792) 107508 + [1385380,1505091) 119711
    # + [1533957,1534558) 601
    assert trace.busy_ns(events["devices"][0]["ops"]) == 228800.0
    assert trace.merge([(0, 4), (2, 3), (4, 6), (8, 9)]) == [(0, 6), (8, 9)]


def test_kernel_time_by_name(events):
    ops = events["devices"][0]["ops"]
    assert trace.kernel_ns(ops, ("adc_quantize_pallas_population",)) \
        == 119711.0
    assert trace.kernel_ns(ops, ("mc_adc_eval_pallas_population",)) == 0.0


def test_idle_gaps_named_by_host(events):
    d = events["devices"][0]
    # gaps 2 + 2 + 1 + 1276588 ns have no host event over their middles;
    # the 28866 ns gap (middle 1519524) lies inside bench.explore
    assert trace.idle_gaps(d["ops"], events["host"]) == [
        ("no host event", 1276593e-9), ("bench.explore", 28866e-9)]


def test_top_ops_leave_containers_out(events):
    d = events["devices"][0]
    top = trace.top_ops(d["ops"], d["modules"], k=2)
    assert top == [("jit_f/adc_quantize_pallas_population.1", 119711e-9),
                   ("jit_f/fusion.13", 107508e-9)]
    assert all("while" not in name
               for name, _ in trace.top_ops(d["ops"], d["modules"]))


def test_summary(events):
    s = trace.summarize(events, window_s=0.01)
    assert s["busy_s"] == pytest.approx(228800e-9)
    assert s["window_s"] == 0.01
    assert len(s["breakdown"]["device_ops"]) == 6
