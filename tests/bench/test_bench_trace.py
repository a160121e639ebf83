"""The trace reduction: busy union, idle share, program and kernel sums,
top operations and labelled idle gaps, checked by hand on a tiny trace
and on a small trace recorded on a TPU v5e (``data/``)."""

import json
import pathlib

import pytest

import trace as trace_lib

DATA = pathlib.Path(__file__).resolve().parent / "data"

NAMES = {"op_lines": ["XLA Ops"], "module_lines": ["XLA Modules"],
         "programs": {"decode": ["jit__dec"], "prefill": ["jit__pre"]},
         "kernels": {"paged_attention": ["paged_attention"]}}

# times in ns: decode program 0-100 with two ops overlapping, idle
# 100-300 while the host steps, prefill 300-700, idle 700-1000
TINY = {
    "/device:TPU:0": {
        "XLA Modules": [["jit__dec(1)", 0, 100], ["jit__pre(2)", 300, 400]],
        "XLA Ops": [["fusion.1", 0, 60], ["paged_attention_kernel", 40, 60],
                    ["fusion.2", 300, 250], ["fusion.2", 550, 150]],
    },
    "/host:CPU": {
        "main": [["bench.step", 0, 290], ["bench.wait_arrival", 700, 300],
                 ["PjitFunction(_pre)", 150, 100]],
    },
}


def test_tiny_trace_by_hand():
    r = trace_lib.reduce_events(TINY, 1000e-9, NAMES)
    assert r["busy_s"] == pytest.approx(500e-9)      # 0-100 and 300-700
    assert r["program_s"] == pytest.approx({"decode": 100e-9,
                                            "prefill": 400e-9})
    assert r["program_n"] == {"decode": 1, "prefill": 1}
    assert r["kernel_s"] == pytest.approx({"paged_attention": 60e-9})
    assert r["kernel_n"] == {"paged_attention": 1}
    assert r["device_ops"][0] == ["fusion", pytest.approx(460e-9)]
    # the one gap between busy stretches, labelled by the innermost host
    # span covering its middle (200 ns: the prefill's dispatch)
    assert r["idle_gaps"] == [["PjitFunction(_pre)", pytest.approx(200e-9)]]


def test_union_merges_overlaps():
    assert trace_lib._union([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [(0, 4), (5, 10)]


def test_no_device_events_reads_nothing():
    r = trace_lib.reduce_events({"/host:CPU": TINY["/host:CPU"]}, 1.0, NAMES)
    assert r["busy_s"] is None


def test_recorded_v5e_trace():
    """A slice of a traced phi3-mini.chat window on a TPU v5e, kept as
    plain events; its expected numbers were worked out when it was
    recorded (``expected`` in the file)."""
    rec = json.loads((DATA / "v5e_chat_slice.json").read_text())
    r = trace_lib.reduce_events(rec["events"], rec["window_s"])
    exp = rec["expected"]
    assert r["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert r["program_n"] == exp["program_n"]
    assert r["kernel_n"] == exp["kernel_n"]
    assert 0 < r["busy_s"] <= r["window_s"]
    for k, v in exp["program_s"].items():
        assert r["program_s"][k] == pytest.approx(v, rel=1e-9)
    for k, v in exp["kernel_s"].items():
        assert r["kernel_s"][k] == pytest.approx(v, rel=1e-9)
