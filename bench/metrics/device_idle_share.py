"""Device: share of the traced stretch in which no operation ran on the
chip (one minus the union of the device's operation intervals over the
stretch)."""


def read(run):
    t = run.trace or {}
    if not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
