"""Engine: host milliseconds per engine iteration outside the token fetch,
the mean over the traced stretch's ``engine.step`` spans of each span's
duration less its ``engine.fetch``. Hidden behind the device step or not,
it is what the gap between tokens becomes once the step is shorter
(``spans.py``)."""

from spans import for_run


def read(run):
    r = for_run(run)
    return r["engine_host_ms"] if r else None
