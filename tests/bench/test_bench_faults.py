"""The correctness check against faults planted in the timed path.

Each test skips the harness's look for a chip and drives the rest of a
run at rehearsal sizes on the CPU: the engine is built as the cell says
and then broken underneath, and ``correct`` has to come out false. A
sound run of the same cell and seed comes out true, so the fault is what
flips it. The faults a serving cell on one chip can have: a token
altered where it is produced, and a decode step that returns its KV state
unchanged. (Half a batch left out of a mean and a missing exchange between
chips belong to training and to cells on several chips.)
"""

import time

import jax
import jax.numpy as jnp
import pytest

import harness

CELL = "phi3-mini.chat"
SEED = 2**33 + 17


def _run(monkeypatch, breaker=None):
    build = harness.build_engine

    def broken(*a, **kw):
        engine = build(*a, **kw)
        if breaker is not None:
            breaker(engine.backend)
        return engine

    monkeypatch.setattr(harness, "build_engine", broken)
    c = harness.rehearsal_cell(harness.cell(CELL))
    return harness.run_cell(c, seed=SEED, seconds=2, trace=False,
                            t_start=time.perf_counter(), log=lambda s: None)


def alter_token(backend):
    """Every decoded token is replaced by the next id."""
    decode = backend.decode
    vocab = backend.arch.cfg.vocab

    def wrong(*a, **kw):
        tok = decode(*a, **kw)
        backend.last_tok = (tok + 1) % vocab
        return backend.last_tok

    backend.decode = wrong


def keep_state(backend):
    """The decode step computes on a copy of the KV pool and hands the
    old pool back: nothing a step writes survives it."""
    step = backend._decode_fn

    def unchanged(p, qp, cache, *rest):
        tok, _ = step(p, qp, jax.tree.map(jnp.copy, cache), *rest)
        return tok, cache

    backend._decode_fn = unchanged


def test_sound_run_is_correct(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("breaker", [alter_token, keep_state],
                         ids=["token_altered", "state_unchanged"])
def test_fault_is_not_correct(monkeypatch, breaker):
    res = _run(monkeypatch, breaker)
    assert not res["correct"], res["checks"]
