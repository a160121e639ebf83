"""Operations and bytes the work needs, from shapes alone.

These are the algorithm's counts, not what a program happens to execute:
a padded prefill row, an empty decode slot or a lane of padding costs
nothing here. So a program that stops wasting them reads as a gain, and
the counts do not go stale when the layout changes.
"""

from __future__ import annotations

from typing import Iterable


def layer_params(m: dict) -> int:
    """Weights of one decoder layer's matrix products."""
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def attention_flops(m: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys,
    summed over the layers."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def decode_token_flops(m: dict, context: int) -> int:
    """One decoded token that attends ``context`` positions (itself
    included): every layer, the output head, causal attention."""
    dense = m["n_layers"] * layer_params(m) + m["vocab"] * m["d_model"]
    return 2 * dense + attention_flops(m, context)


def prefill_flops(m: dict, n: int) -> int:
    """A prompt of ``n`` tokens: every layer for every token, causal
    attention (token ``i`` attends ``i`` positions), and the output head
    for the last position only, which is all a prefill needs."""
    return (2 * m["n_layers"] * layer_params(m) * n
            + 2 * m["vocab"] * m["d_model"]
            + attention_flops(m, n * (n + 1) // 2))


def paged_attention_call(m: dict, lens: Iterable[int], block_len: int,
                         kv_bytes: int = 2) -> tuple:
    """(operations, bytes) of one paged-attention kernel call (one layer)
    over decode rows that attend ``lens`` positions each. Bytes are the
    K and V the rows need, at the unpadded head size, over their valid
    positions rounded up to whole blocks, plus the queries read and the
    outputs written."""
    lens = list(lens)
    hd, hq, hkv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    ops = sum(4 * hq * hd * n for n in lens)
    kv = sum(-(-n // block_len) * block_len for n in lens)
    nbytes = 2 * kv * hkv * hd * kv_bytes + 2 * len(lens) * hq * hd * 2
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, the bound that sets it)."""
    t_ops = ops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
