"""Host spans of the serving engine, on the JAX profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation``: a TraceMe event that
costs about a microsecond when no profiler runs. While one runs (any
``jax.profiler.trace(...)`` or ``start_trace`` capture, TensorBoard's
included), the profiler keeps the spans in memory and writes them into
the same ``.xplane.pb`` as the device's operations, on the same clock.
Counters ride on a span as its keyword arguments (or ``set_metadata``
for those known only when the span closes) and land as the event's
stats. Spans on the engine's one host thread nest, so each span's parent
is the span that encloses it; ``rid`` ties one request's admission and
prefill spans together across iterations.

Each span below: where it opens; its counters; what reads it.

``engine.step``
    All of ``LLMEngine._step``. ``iteration``, ``running`` (decode rows),
    ``chunking`` (rows mid-prefill), ``queued``, ``written_tokens`` (the
    running rows' sequence lengths before the step), ``reserved_blocks``
    (pool blocks held or reserved before the step). Read by
    ``engine_host_ms`` and ``idle_in_engine_share``.
``engine.sampling``
    Building one dispatch's sampling vectors. ``sampling`` (0/1: a row
    samples). Operator only.
``backend.begin_iteration``
    Block growth, prefix publishing, copy-on-write before the decode
    dispatch. ``grown`` (blocks grown). Operator only.
``backend.tables``
    The block tables' upload, when a table changed. ``slots``. Operator
    only.
``backend.decode`` / ``backend.verify``
    The decode (or speculative verify) step's dispatch. ``rows``; verify
    adds ``drafted`` (draft tokens). Operator only.
``engine.schedule``
    The admission pass, forced admission and preemption. ``admitted``,
    ``blocked`` (0/1: the pass stopped on pool capacity). Operator only.
``engine.admit``
    One admission, inside ``engine.schedule``. ``rid``,
    ``prompt_tokens``, ``waited_us`` (admission time minus
    ``submitted_at``). Waits for a cell with enough admissions.
``backend.prefill`` / ``backend.prefill_chunk``
    A monolithic prefill's dispatch / one budgeted chunk's, inside
    ``engine.admit`` (or, for a chunk continuation, ``engine.step``).
    ``rid``, ``tokens``, ``width`` (the compiled width). Waits for a cell
    with enough admissions.
``engine.fetch``
    The one device-to-host token fetch. ``arrays``. Read by
    ``idle_in_fetch_share`` and ``engine_host_ms``.
``engine.finish``
    The host finish bookkeeping after the fetch. ``finished``,
    ``tokens`` (committed). Operator only.

The metrics named are the benchmark's (``bench/spans.py``); "operator
only" spans are for a person reading a trace. Counters are values the
engine already holds; the two that cost a sum (``written_tokens`` and
``reserved_blocks``) are gathered only while a profiler records
(:func:`enabled`).
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

ENGINE_STEP = "engine.step"
ENGINE_SAMPLING = "engine.sampling"
BACKEND_BEGIN_ITERATION = "backend.begin_iteration"
BACKEND_TABLES = "backend.tables"
BACKEND_DECODE = "backend.decode"
BACKEND_VERIFY = "backend.verify"
ENGINE_SCHEDULE = "engine.schedule"
ENGINE_ADMIT = "engine.admit"
BACKEND_PREFILL = "backend.prefill"
BACKEND_PREFILL_CHUNK = "backend.prefill_chunk"
ENGINE_FETCH = "engine.fetch"
ENGINE_FINISH = "engine.finish"

NAMES = (ENGINE_STEP, ENGINE_SAMPLING, BACKEND_BEGIN_ITERATION,
         BACKEND_TABLES, BACKEND_DECODE, BACKEND_VERIFY, ENGINE_SCHEDULE,
         ENGINE_ADMIT, BACKEND_PREFILL, BACKEND_PREFILL_CHUNK, ENGINE_FETCH,
         ENGINE_FINISH)


def span(name: str, **counters: int) -> TraceAnnotation:
    """A host span named ``name`` carrying integer ``counters``; use it as
    a context manager. ``set_metadata(**more)`` on it adds counters
    before it closes."""
    return TraceAnnotation(name, **counters)


def enabled() -> bool:
    """True while a profiler records host spans."""
    return TraceAnnotation.is_enabled()
