"""Engine: share of the traced stretch in which the device sat idle while
the engine's host thread was inside its token fetch (``engine.fetch``):
the fetch's exposed latency. Read from the program's spans on the
profiler's clock (``spans.py``)."""

from spans import for_run


def read(run):
    r = for_run(run)
    return 100.0 * r["idle_in_fetch_s"] / r["window_s"] if r else None
