"""The generator: one schedule per seed, lengths inside the clip, and the
same work for every seed in another order."""

import json
import pathlib

import pytest

import loadgen

TRAFFIC = pathlib.Path(__file__).resolve().parents[2] / "bench" / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    tr = mix(name)
    seed = 2**31 + 12345
    assert loadgen.schedule(tr, seed, 10) == loadgen.schedule(tr, seed, 10)
    a = loadgen.prompts(loadgen.schedule(tr, seed, 10), 32064, seed)
    b = loadgen.prompts(loadgen.schedule(tr, seed, 10), 32064, seed)
    assert all((x == y).all() for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_the_clip(name):
    tr = mix(name)
    for a in loadgen.schedule(tr, 7, 30):
        assert tr["prompt_tokens"]["min"] <= a.prompt_len \
            <= tr["prompt_tokens"]["max"]
        assert tr["output_tokens"]["min"] <= a.output_len \
            <= tr["output_tokens"]["max"]
        assert a.output_len <= tr["max_new_tokens"]


def _window(tr, s, seconds):
    w0 = tr["warmup_s"]
    return [a for a in s if w0 <= a.due_s < w0 + seconds]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_window_work_in_another_order(name):
    tr = mix(name)
    seconds = 50
    s1, s2 = (_window(tr, loadgen.schedule(tr, seed, seconds), seconds)
              for seed in (1, 2**40 + 1))
    assert [a.catalog_idx for a in s1] != [a.catalog_idx for a in s2]
    assert [a.due_s for a in s1] != [a.due_s for a in s2]
    n = round(tr["rate_rps"] * seconds)
    for s in (s1, s2):
        # the window holds the catalog's first n entries, one each
        assert sorted(a.catalog_idx for a in s) == list(range(n))


def test_stretches_hold_their_expected_counts():
    tr = mix("chat")
    s = loadgen.schedule(tr, 3, 50)
    w0, w1 = tr["warmup_s"], tr["warmup_s"] + 50
    assert sum(a.due_s < w0 for a in s) == round(tr["rate_rps"] * w0)
    assert sum(w0 <= a.due_s < w1 for a in s) == round(tr["rate_rps"] * 50)
    assert max(a.due_s for a in s) < loadgen.horizon_s(tr, 50)
    assert [a.due_s for a in s] == sorted(a.due_s for a in s)


def test_large_seeds_do_not_collide():
    tr = mix("chat")
    a = loadgen.schedule(tr, 2**32 + 5, 10)
    b = loadgen.schedule(tr, 5, 10)
    assert [x.due_s for x in a] != [x.due_s for x in b]
