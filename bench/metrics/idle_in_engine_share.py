"""Engine: share of the traced stretch in which the device sat idle while
the engine's host thread was inside an iteration (``engine.step``) but not
in its token fetch: scheduling, table uploads, sampling vectors, dispatch
and finish bookkeeping the device waited on (``spans.py``)."""

from spans import for_run


def read(run):
    r = for_run(run)
    return 100.0 * r["idle_in_engine_s"] / r["window_s"] if r else None
