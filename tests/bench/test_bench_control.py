"""The correctness control at a size a test run holds: the program's own
int8 serving path (int8 weights and int8 KV blocks, the step below the
configuration's bf16) comes out not correct against the configuration's
limit, where the bf16 path on the same seed comes out correct. On the
chip the same control runs at the cell's own size (``bench/control.py``;
readings in PERF.md)."""

import time

import pytest

import harness

SEED = 2**32 + 99


@pytest.mark.parametrize("quant,correct", [(False, True), (True, False)],
                         ids=["bf16", "int8_control"])
def test_int8_control_fails_the_limit(quant, correct):
    c = harness.rehearsal_cell(harness.cell("phi3-mini.chat"))
    c["traffic"]["check_tokens"] = 400     # every served token of the window
    res = harness.run_cell(c, seed=SEED, seconds=6, trace=False,
                           t_start=time.perf_counter(), serve_quant=quant,
                           log=lambda s: None)
    assert res["correct"] is correct, res["checks"]
