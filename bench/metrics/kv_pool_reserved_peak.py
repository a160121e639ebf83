"""Cache manager: the largest share of the KV pool's usable blocks that
admitted requests held or had reserved after any step of the window."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    used = max(s.reserved_blocks for s in steps)
    return 100.0 * used / run.counters["pool_usable_blocks"]
