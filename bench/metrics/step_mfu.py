"""Whole step: model FLOPs of the work done in the window over the window
and the chip's bf16 peak. The work is every prompt prefilled and every
token decoded by steps that returned in the window, counted by
``flops.prefill_flops`` and ``flops.decode_token_flops`` (attention at
each token's own context)."""

from flops import decode_token_flops, prefill_flops


def read(run):
    if not run.peaks:
        return None
    work = 0
    for s in run.window_steps():
        work += sum(decode_token_flops(run.sizes, n) for n in s.lens)
        work += sum(prefill_flops(run.sizes, n) for n in s.prefill_tokens)
    return 100.0 * work / (run.seconds * run.peaks["bf16_flops"])
