"""Cache manager: mean share of the KV pool's usable blocks that admitted
requests hold or have reserved for their worst case, over the window's
steps (the allocator's count after each step). What admission sees as
full."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    used = sum(s.reserved_blocks for s in steps) / len(steps)
    return 100.0 * used / run.counters["pool_usable_blocks"]
