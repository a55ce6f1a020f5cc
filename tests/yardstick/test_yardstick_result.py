"""The last line: exactly the contract's keys, every digit kept."""

import json

import pytest

from benchmark import result

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 123}
METRICS = {"tok_s_chip": {"value": 27123.456789, "unit": "tokens/s"},
           "setup_s": {"value": 31.25, "unit": "s"}}


def test_untraced_line_has_exactly_five_keys():
    line = json.loads(result.dumps(result.build(True, 80, 0, METRICS, DEVICE)))
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["metrics"]["tok_s_chip"] == {"value": 27123.456789, "unit": "tokens/s"}
    assert "\n" not in result.dumps(line)


def test_traced_line_adds_breakdown_capped_at_ten():
    ops = [[f"fusion.{i}", 0.1 * i] for i in range(14)]
    line = result.build(True, 80, 0, METRICS, dict(DEVICE, busy_s=1.0, window_s=2.0),
                        {"device_ops": ops, "idle_gaps": [("launch", 0.5)]})
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "breakdown"}
    assert len(line["breakdown"]["device_ops"]) == 10
    assert line["breakdown"]["idle_gaps"] == [["launch", 0.5]]


@pytest.mark.parametrize("bad", [float("nan"), float("inf"), None, True, "3"])
def test_a_metric_without_a_finite_number_is_a_bug(bad):
    with pytest.raises(ValueError):
        result.build(True, 1, 0, {"x": {"value": bad, "unit": "s"}}, DEVICE)
