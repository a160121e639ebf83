"""Engine: decode rows per decode dispatch over the window's steps (every
step with a running request makes exactly one batched decode
dispatch)."""


def read(run):
    rows = [s.decode_rows for s in run.window_steps() if s.decode_rows]
    return sum(rows) / len(rows) if rows else None
