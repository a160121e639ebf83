"""Per-layer readers that take their numbers from the benchmark's step
records and the block allocator, checked by hand."""

import types

import pytest

import harness
from repro.models.cache import BlockAllocator, PagedLayout


def _engine(*allocs):
    backend = types.SimpleNamespace(
        alloc=None if len(allocs) > 1 else allocs[0],
        allocs=list(allocs) if len(allocs) > 1 else None)
    return types.SimpleNamespace(backend=backend)


@pytest.mark.parametrize("devices", [1, 2])
def test_reserved_blocks_count_the_worst_case(devices):
    allocs = [BlockAllocator(PagedLayout(16, 9, 64)) for _ in range(devices)]
    eng = _engine(*allocs)
    assert harness.reserved_blocks(eng) == 0
    # one block written now, three reserved for the request's worst case
    held = allocs[-1].admit(1, 1, 3)
    try:
        assert len(held) == 1
        assert harness.reserved_blocks(eng) == 3
    finally:
        allocs[-1].release(1)
    assert harness.reserved_blocks(eng) == 0


def _run(steps):
    step = types.SimpleNamespace
    steps = [step(decode_rows=len(lens), lens=lens, reserved_blocks=r)
             for lens, r in steps]
    return types.SimpleNamespace(
        window_steps=lambda: steps,
        counters={"pool_usable_blocks": 8, "block_len": 16})


@pytest.mark.parametrize("name,expected", [
    ("kv_pool_reserved_mean", 100.0 * 3 / 8),
    ("kv_pool_reserved_peak", 100.0 * 4 / 8),
    ("kv_pool_written_mean", 100.0 * (0 + 20 + 32) / 3 / 128),
    ("batch_occupancy", 1.5),
])
def test_pool_and_batch_readers_by_hand(name, expected):
    # three steps: no running row, one row of 20 tokens, two of 10 and 22
    run = _run([([], 2), ([20], 3), ([10, 22], 4)])
    assert harness.metric_reader(name)(run) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["kv_pool_reserved_mean",
                                  "kv_pool_reserved_peak",
                                  "kv_pool_written_mean",
                                  "batch_occupancy"])
def test_empty_window_reads_nothing(name):
    assert harness.metric_reader(name)(_run([])) is None
