"""Model step: device milliseconds per decode-step program in the traced
stretch (the program's module events, named in ``programs.json``)."""


def read(run):
    t = run.trace or {}
    n = t.get("program_n", {}).get("decode")
    return t["program_s"]["decode"] / n * 1e3 if n else None
