"""Cache manager: mean share of the KV pool's usable tokens that hold
written K/V (the running requests' sequence lengths before each step),
over the window's steps. Reserved space not yet written is left out."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    c = run.counters
    held = sum(sum(s.lens) for s in steps) / len(steps)
    return 100.0 * held / (c["pool_usable_blocks"] * c["block_len"])
