"""Kernels: the paged-attention decode kernel's share of its roofline in
the traced stretch. Per decode step the least time is the larger of its
operations over the bf16 peak and its logical K/V bytes over HBM
bandwidth (``flops.paged_attention_call``, once per layer, for the rows
that decode); the time is the kernel's device time per decode-step
program."""

from flops import paged_attention_call, roofline_seconds


def read(run):
    t = run.trace or {}
    n = t.get("program_n", {}).get("decode")
    ks = t.get("kernel_s", {}).get("paged_attention")
    lens = t.get("decode_lens") or []
    if not (n and ks and lens and run.peaks):
        return None
    bound = []
    for step in lens:
        ops, nbytes = paged_attention_call(run.sizes, step,
                                           run.counters["block_len"])
        s, _ = roofline_seconds(ops, nbytes, run.peaks)
        bound.append(s * run.sizes["n_layers"])
    return 100.0 * (sum(bound) / len(bound)) / (ks / n)
