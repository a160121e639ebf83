"""Per-kernel shape/dtype sweeps: interpret-mode Pallas vs pure-jnp oracle."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

# ---------------------------------------------------------------------------
# int8 GEMM
# ---------------------------------------------------------------------------


# (8, ...) is a decode batch and (300, ...) a prefill length that no
# row tile divides: the kernel pads the rows to its fitted row block
@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (128, 256, 128),
                                   (256, 512, 256), (64, 1024, 128),
                                   (8, 256, 128), (300, 512, 256)])
@pytest.mark.parametrize("activation", ["none", "relu"])
def test_int8_gemm_bit_exact(m, k, n, activation):
    from repro.kernels.int8_gemm.ops import QuantizedLinearParams, int8_gemm

    rng = np.random.default_rng(m * n)
    w = rng.standard_normal((k, n), np.float32) / np.sqrt(k)
    p = QuantizedLinearParams.from_float(
        jnp.asarray(w), jnp.asarray(rng.standard_normal(n) * 0.05), 0.04, 0.04)
    xq = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    y_ref = int8_gemm(xq, p, activation=activation, backend="xla")
    y_pal = int8_gemm(xq, p, activation=activation, backend="interpret")
    assert (np.asarray(y_ref) == np.asarray(y_pal)).all()


def test_int8_gemm_gelu_bit_exact():
    from repro.kernels.int8_gemm.ops import QuantizedLinearParams, int8_gemm

    rng = np.random.default_rng(7)
    k, n = 128, 64
    w = rng.standard_normal((k, n), np.float32) / np.sqrt(k)
    p = QuantizedLinearParams.from_float(jnp.asarray(w), jnp.zeros(n), 0.04, 0.04)
    xq = jnp.asarray(rng.integers(-127, 128, (64, k)), jnp.int8)
    kw = dict(activation="gelu", act_scales=(0.04, 0.04))
    y_ref = int8_gemm(xq, p, backend="xla", **kw)
    y_pal = int8_gemm(xq, p, backend="interpret", **kw)
    assert (np.asarray(y_ref) == np.asarray(y_pal)).all()


def test_int8_gemm_quant_error_vs_float():
    from repro.kernels.int8_gemm.ops import QuantizedLinearParams, int8_gemm
    from repro.kernels.int8_gemm.ref import gemm_float_ref

    rng = np.random.default_rng(3)
    m, k, n = 128, 256, 64
    x = rng.standard_normal((m, k), np.float32)
    w = rng.standard_normal((k, n), np.float32) / np.sqrt(k)
    s_in = float(np.abs(x).max() / 127)
    y_f = np.asarray(gemm_float_ref(jnp.asarray(x), jnp.asarray(w), jnp.zeros(n)))
    s_out = float(np.abs(y_f).max() / 127)
    p = QuantizedLinearParams.from_float(jnp.asarray(w), jnp.zeros(n), s_in, s_out)
    xq = jnp.asarray(np.clip(np.round(x / s_in), -127, 127), jnp.int8)
    y_q = np.asarray(int8_gemm(xq, p, backend="xla"), np.float32) * s_out
    rel = np.abs(y_q - y_f).max() / np.abs(y_f).max()
    assert rel < 0.05


# ---------------------------------------------------------------------------
# ITA attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,d,causal,hkv", [
    (128, 64, False, 4), (128, 64, True, 4), (256, 64, True, 2),
    (256, 128, True, 1),
])
def test_ita_attention_bit_exact(s, d, causal, hkv):
    from repro.kernels.ita_attention.ops import ita_attention

    rng = np.random.default_rng(s + d)
    b, h = 1, 4
    q = jnp.asarray(rng.integers(-127, 128, (b, h, s, d)), jnp.int8)
    k = jnp.asarray(rng.integers(-127, 128, (b, hkv, s, d)), jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, (b, hkv, s, d)), jnp.int8)
    kw = dict(qk_scale=1e-3, v_scale=0.03, out_scale=0.02, causal=causal)
    y1 = ita_attention(q, k, v, backend="xla", **kw)
    y2 = ita_attention(q, k, v, backend="interpret", **kw)
    assert (np.asarray(y1) == np.asarray(y2)).all()


def test_ita_attention_accuracy_near_int8_bound():
    """Kernel error ≈ the float-softmax-with-8-bit-probs information bound."""
    from repro.kernels.ita_attention.ops import ita_attention
    from repro.kernels.ita_attention.ref import attention_float_ref

    rng = np.random.default_rng(0)
    b, h, s, d = 1, 4, 256, 64
    sc = 0.03
    q = np.clip(np.round(rng.standard_normal((b, h, s, d)) / np.sqrt(d) / sc),
                -127, 127).astype(np.int8)
    k = np.clip(np.round(rng.standard_normal((b, h, s, d)) / sc), -127, 127).astype(np.int8)
    v = np.clip(np.round(rng.standard_normal((b, h, s, d)) / sc), -127, 127).astype(np.int8)
    out_scale = 0.02
    y = np.asarray(ita_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), qk_scale=sc * sc,
        v_scale=sc, out_scale=out_scale, causal=True,
        backend="xla")).astype(np.float32) * out_scale
    y_f = np.asarray(attention_float_ref(
        jnp.asarray((q * sc).astype(np.float32).reshape(b * h, s, d)),
        jnp.asarray((k * sc).astype(np.float32).reshape(b * h, s, d)),
        jnp.asarray((v * sc).astype(np.float32).reshape(b * h, s, d)),
        scale=1.0, causal=True)).reshape(b, h, s, d)
    rms = np.sqrt(((y - y_f) ** 2).mean())
    assert rms / y_f.std() < 0.10


# ---------------------------------------------------------------------------
# int softmax
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,cols", [(64, 128), (256, 512)])
def test_int_softmax_kernel_bit_exact(rows, cols):
    from repro.kernels.int_softmax.ops import int_softmax

    rng = np.random.default_rng(rows)
    lq = jnp.asarray(rng.integers(-127, 128, (rows, cols)), jnp.int8)
    y1 = int_softmax(lq, logit_scale=0.06, backend="xla")
    y2 = int_softmax(lq, logit_scale=0.06, backend="interpret")
    assert (np.asarray(y1) == np.asarray(y2)).all()


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,chunk", [(128, 32), (256, 64), (192, 64)])
def test_ssd_scan_vs_sequential_oracle(s, chunk):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    rng = np.random.default_rng(s)
    B, H, P, G, N = 2, 4, 16, 2, 16
    dta = jnp.asarray(-rng.random((B, H, s), np.float32) * 0.1)
    x = jnp.asarray(rng.standard_normal((B, H, s, P), np.float32))
    bm = jnp.asarray(rng.standard_normal((B, G, s, N), np.float32) * 0.3)
    cm = jnp.asarray(rng.standard_normal((B, G, s, N), np.float32) * 0.3)
    y_ref = np.asarray(ssd_scan_ref(dta, x, bm, cm))
    y_xla = np.asarray(ssd_scan(dta, x, bm, cm, chunk=chunk, backend="xla"))
    np.testing.assert_allclose(y_xla, y_ref, rtol=2e-4, atol=2e-5)
    if s % chunk == 0:
        y_pal = np.asarray(ssd_scan(dta, x, bm, cm, chunk=chunk,
                                    backend="interpret"))
        np.testing.assert_allclose(y_pal, y_ref, rtol=2e-4, atol=2e-5)


def test_ssd_decode_step_matches_scan():
    from repro.kernels.ssd_scan.ref import ssd_decode_step, ssd_scan_ref

    rng = np.random.default_rng(5)
    B, H, S, P, N = 1, 2, 16, 8, 8
    dta = jnp.asarray(-rng.random((B, H, S), np.float32) * 0.2)
    x = jnp.asarray(rng.standard_normal((B, H, S, P), np.float32))
    bm = jnp.asarray(rng.standard_normal((B, 1, S, N), np.float32))
    cm = jnp.asarray(rng.standard_normal((B, 1, S, N), np.float32))
    y_scan = np.asarray(ssd_scan_ref(dta, x, bm, cm))
    state = jnp.zeros((B, H, N, P), jnp.float32)
    for t in range(S):
        bh = jnp.repeat(bm[:, :, t], H, 1)
        ch = jnp.repeat(cm[:, :, t], H, 1)
        state, y_t = ssd_decode_step(state, dta[:, :, t], x[:, :, t], bh, ch)
    np.testing.assert_allclose(np.asarray(y_t), y_scan[:, :, -1], rtol=2e-4,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,chunk", [(256, 64), (512, 128)])
def test_rglru_vs_oracle(s, chunk):
    from repro.kernels.rglru.ops import rglru
    from repro.kernels.rglru.ref import rglru_ref

    rng = np.random.default_rng(s)
    B, D = 2, 32
    log_a = jnp.asarray(-np.abs(rng.standard_normal((B, s, D))) * 0.1,
                        jnp.float32)
    u = jnp.asarray(rng.standard_normal((B, s, D)), jnp.float32)
    y_ref = np.asarray(rglru_ref(log_a, u))
    y_pal = np.asarray(rglru(log_a, u, chunk=chunk, backend="interpret"))
    y_xla = np.asarray(rglru(log_a, u, backend="xla"))
    np.testing.assert_allclose(y_pal, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_xla, y_ref, rtol=1e-4, atol=1e-5)


def test_rglru_decode_matches_scan():
    from repro.kernels.rglru.ref import rglru_decode_step, rglru_ref

    rng = np.random.default_rng(9)
    B, S, D = 1, 32, 16
    log_a = jnp.asarray(-np.abs(rng.standard_normal((B, S, D))) * 0.2,
                        jnp.float32)
    u = jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)
    y = np.asarray(rglru_ref(log_a, u))
    h = jnp.zeros((B, D), jnp.float32)
    for t in range(S):
        h, out = rglru_decode_step(h, log_a[:, t], u[:, t])
    np.testing.assert_allclose(np.asarray(out), y[:, -1], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Paged decode attention over a stacked pool (layer-indexed entry)
# ---------------------------------------------------------------------------


def _stacked_case(int8, blk, seed=0):
    """Pools [L, N, Hkv, blk, D], queries, and a table over blocks 1..N-1
    (block 0 is the trash block) with an empty row (len 0)."""
    rng = np.random.default_rng(seed)
    L, B, HQ, HKV, D, N, M = 3, 3, 8, 2, 16, 7, 3
    shape = (L, N, HKV, blk, D)
    if int8:
        kp = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    else:
        kp = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, HQ, 1, D)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(np.arange(1, N))[:B * M].reshape(B, M)
                      if B * M < N else rng.integers(1, N, (B, M)), jnp.int32)
    lens = jnp.asarray([blk * M - 1, 0, blk + 1], jnp.int32)
    return kp, vp, q, tbl, lens


# blk 4 keeps the pool row-major; blk 128 with D 16 is stored block-minor
# on a TPU, so the kernel reads the transposed view
@pytest.mark.parametrize("blk", [4, 128])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("ring", [False, True], ids=["full", "ring"])
def test_paged_attention_layer_matches_layer_slice(int8, blk, ring):
    """The layer-indexed call on the stack equals the 4-D call on
    ``pool[l]`` for every layer, bit for bit, and the oracle within flash
    reordering — empty row, ring table with start and window included."""
    from repro.kernels.paged_attention.kernel import block_minor
    from repro.kernels.paged_attention.ops import (
        paged_attention, paged_attention_int8,
    )
    from repro.kernels.paged_attention.ref import (
        paged_attention_int8_dequant_ref, paged_attention_ref,
    )

    assert block_minor(blk, 16) == (blk == 128)
    kp, vp, q, tbl, lens = _stacked_case(int8, blk)
    kw = {}
    if ring:
        # rows attend a window of one block and a bit behind their length,
        # the ring table's entry 0 sitting one block back
        kw = dict(window=blk + 2,
                  start=jnp.asarray([blk, 0, 0], jnp.int32))
        lens = jnp.asarray([blk * 3, 0, blk + 1], jnp.int32)
    n = kp.shape[1]
    scale = jnp.stack([jnp.linspace(0.01, 0.03, n, dtype=jnp.float32)] * 3)
    for layer in range(kp.shape[0]):
        if int8:
            stacked = paged_attention_int8(
                q, kp, vp, tbl, lens, k_scale=scale, v_scale=scale * 2,
                layer=jnp.int32(layer), backend="interpret", **kw)
            flat = paged_attention_int8(
                q, kp[layer], vp[layer], tbl, lens, k_scale=scale[layer],
                v_scale=scale[layer] * 2, backend="interpret", **kw)
            ref = paged_attention_int8_dequant_ref(
                q, kp[layer], vp[layer], tbl, lens, k_scale=scale[layer],
                v_scale=scale[layer] * 2, **kw)
        else:
            stacked = paged_attention(q, kp, vp, tbl, lens,
                                      layer=jnp.int32(layer),
                                      backend="interpret", **kw)
            flat = paged_attention(q, kp[layer], vp[layer], tbl, lens,
                                   backend="interpret", **kw)
            ref = paged_attention_ref(q, kp[layer], vp[layer], tbl, lens,
                                      **kw)
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(flat))
        np.testing.assert_allclose(np.asarray(stacked), np.asarray(ref),
                                   atol=3e-6, rtol=3e-5)
        assert float(jnp.abs(stacked[1]).max()) == 0.0   # the empty row


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_attention_layer_xla_matches_layer_slice(int8):
    """The ``xla`` oracle takes the same ``layer`` argument."""
    from repro.kernels.paged_attention.ops import (
        paged_attention, paged_attention_int8,
    )

    kp, vp, q, tbl, lens = _stacked_case(int8, 4, seed=1)
    op = paged_attention_int8 if int8 else paged_attention
    for layer in range(kp.shape[0]):
        stacked = op(q, kp, vp, tbl, lens, layer=jnp.int32(layer),
                     backend="xla")
        flat = op(q, kp[layer], vp[layer], tbl, lens, backend="xla")
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(flat))


def test_paged_attention_rejects_pool_rank_without_layer():
    from repro.kernels.paged_attention.ops import paged_attention

    kp, vp, q, tbl, lens = _stacked_case(False, 4)
    with pytest.raises(ValueError, match="rank"):
        paged_attention(q, kp, vp, tbl, lens, backend="interpret")
    with pytest.raises(ValueError, match="rank"):
        paged_attention(q, kp[0], vp[0], tbl, lens, layer=jnp.int32(0),
                        backend="interpret")


@pytest.mark.parametrize("ring", [False, True], ids=["full", "ring"])
def test_paged_stack_write_touches_only_its_tokens(ring):
    """The in-place decode write puts each row's token at
    ``[layer, block, :, offset]`` and leaves every other byte of the stack
    as it was: the other layers (trash block included) whole, and in the
    written layer every other block and offset. Empty rows (table zeros)
    write into the layer's trash block only."""
    from repro.models.cache import TRASH_BLOCK
    from repro.models.transformer import _paged_stack_write

    rng = np.random.default_rng(4)
    L, N, HKV, BLK, D, M = 3, 8, 2, 4, 5, 3
    pools = {n: jnp.asarray(rng.standard_normal((L, N, HKV, BLK, D)),
                            jnp.bfloat16) for n in ("k", "v")}
    pools["kscale"] = jnp.ones((L, N), jnp.float32)
    table = jnp.asarray([[3, 5, 6], [0, 0, 0], [2, 7, 1]], jnp.int32)
    pos = jnp.asarray([9, 0, 2], jnp.int32)      # the middle row is empty
    start = None
    if ring:
        # ring rows: entry 0 holds absolute block 1 (positions 4..7)
        start = jnp.asarray([4, 0, 0], jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((3, HKV, 1, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((3, HKV, 1, D)), jnp.float32)
    layer = 1
    out = jax.jit(_paged_stack_write, static_argnums=(6,))(
        pools, k_new, v_new, jnp.int32(layer), pos, table, BLK, start)
    np.testing.assert_array_equal(np.asarray(out["kscale"]),
                                  np.asarray(pools["kscale"]))

    first = np.zeros(3, np.int64) if start is None else np.asarray(start)
    for name, new in (("k", k_new), ("v", v_new)):
        want = np.asarray(pools[name], np.float32).copy()
        for r in range(3):
            bi = (int(pos[r]) - int(first[r])) // BLK
            blk = int(table[r, bi])
            want[layer, blk, :, int(pos[r]) % BLK] = np.asarray(
                new[r, :, 0].astype(jnp.bfloat16), np.float32)
        got = np.asarray(out[name], np.float32)
        np.testing.assert_array_equal(got, want)
        # what changed lies in the written layer only, the trash block
        # of the other layers included
        changed = np.argwhere(got != np.asarray(pools[name], np.float32))
        assert set(changed[:, 0].tolist()) <= {layer}
        for other in set(range(L)) - {layer}:
            np.testing.assert_array_equal(
                got[other, TRASH_BLOCK],
                np.asarray(pools[name], np.float32)[other, TRASH_BLOCK])
