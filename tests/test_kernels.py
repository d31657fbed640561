"""Per-kernel shape/dtype sweeps: pallas(interpret=True) vs pure-jnp oracle."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(atol=2e-2, rtol=2e-2) if dt == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,Sq,Skv,H,KH,Dh", [
    (1, 32, 32, 4, 4, 16),    # MHA
    (2, 64, 64, 8, 2, 32),    # GQA
    (1, 128, 128, 4, 1, 16),  # MQA
    (2, 32, 64, 4, 2, 64),    # cross (Sq != Skv)
    (1, 200, 200, 4, 2, 16),  # past one block, not a multiple: padded
    (1, 144, 176, 4, 2, 16),  # padded cross
])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(B, Sq, Skv, H, KH, Dh, dtype, causal, rng):
    if causal and Sq != Skv:
        pytest.skip("causal requires square here")
    q = jnp.asarray(rng.normal(size=(B, Sq, H, Dh)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Skv, KH, Dh)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Skv, KH, Dh)), dtype)
    o_ref = ref.attention_ref(q, k, v, causal=causal)
    o_pl = ops.flash_attention(q, k, v, causal=causal, impl="pallas")
    o_jnp = ops.flash_attention(q, k, v, causal=causal, impl="jnp")
    np.testing.assert_allclose(np.asarray(o_pl, np.float32),
                               np.asarray(o_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(o_jnp, np.float32),
                               np.asarray(o_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [8, 32])
def test_flash_attention_window(window, rng):
    B, S, H, KH, Dh = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KH, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KH, Dh)), jnp.float32)
    o_ref = ref.attention_ref(q, k, v, causal=True, window=window)
    o_pl = ops.flash_attention(q, k, v, causal=True, window=window,
                               impl="pallas")
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_q_offset(rng):
    """Blockwise attention with a query offset (sequence-parallel shards)."""
    B, S, H, KH, Dh = 1, 64, 2, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KH, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KH, Dh)), jnp.float32)
    full = ref.attention_ref(q, k, v, causal=True)
    half = ops.flash_attention(q[:, 32:], k, v, causal=True, q_offset=32,
                               impl="pallas")
    np.testing.assert_allclose(np.asarray(half), np.asarray(full[:, 32:]),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_and_combine(rng):
    B, S, H, KH, Dh = 3, 48, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KH, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KH, Dh)), jnp.float32)
    kv_len = jnp.array([5, 17, 40])
    o_ref = ref.attention_ref(q, k, v, causal=False, kv_len=kv_len)
    o = ops.decode_attention(q, k, v, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)
    # streaming combine == attention over len+1
    o_old, m_old, l_old = ops.decode_attention(q, k, v, kv_len=kv_len,
                                               return_stats=True)
    k_new = jnp.asarray(rng.normal(size=(B, 1, KH, Dh)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, 1, KH, Dh)), jnp.float32)
    comb = ops.decode_attention_combine(q, o_old, m_old, l_old, k_new, v_new)
    k2, v2 = k, v
    for b in range(B):
        k2 = k2.at[b, int(kv_len[b])].set(k_new[b, 0])
        v2 = v2.at[b, int(kv_len[b])].set(v_new[b, 0])
    o_ref2 = ref.attention_ref(q, k2, v2, causal=False, kv_len=kv_len + 1)
    np.testing.assert_allclose(np.asarray(comb), np.asarray(o_ref2),
                               atol=1e-5)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 32, 2, 16, 1, 8, 8),
    (2, 64, 4, 16, 2, 16, 16),
    (1, 128, 8, 32, 8, 16, 32),
])
@pytest.mark.parametrize("dtype", DTYPES)
def test_ssd_vs_ref(b, s, h, p, g, n, chunk, dtype, rng):
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(b, s, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, g, n)), dtype)
    C = jnp.asarray(rng.normal(size=(b, s, g, n)), dtype)
    y_ref, st_ref = ref.ssd_ref(x, dt, A, B, C)
    y_chk, st_chk = ref.ssd_chunked_jnp(x, dt, A, B, C, chunk=chunk)
    y_pl = ops.ssd(x, dt, A, B, C, chunk=chunk, impl="pallas")
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(y_chk, np.float32),
                               np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st_chk), np.asarray(st_ref),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(y_pl, np.float32),
                               np.asarray(y_ref, np.float32), **tol)


def test_ssd_decode_step_matches_scan(rng):
    b, s, h, p, g, n = 2, 16, 4, 8, 2, 8
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(b, s, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    y_ref, _ = ref.ssd_ref(x, dt, A, B, C)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        state, y = ref.ssd_decode_step(state, x[:, t], dt[:, t], A,
                                       B[:, t], C[:, t])
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_ref), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("w", [8, 64])
def test_rglru_scan_vs_ref(w, rng):
    b, s = 2, 48
    log_a = -jnp.asarray(rng.uniform(0.01, 1.0, size=(b, s, w)), jnp.float32)
    gx = jnp.asarray(rng.normal(size=(b, s, w)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(b, w)), jnp.float32)
    y1, hl1 = ref.rglru_ref(log_a, gx, h0=h0)
    y2, hl2 = ref.rglru_scan_jnp(log_a, gx, h0=h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl1), np.asarray(hl2), atol=1e-5)


@pytest.mark.parametrize("R,L,block", [(1, 2048, 256), (4, 4096, 512),
                                       (3, 1024, 1024)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_topk_compress_pallas_vs_oracle(R, L, block, dtype, rng):
    x = jnp.asarray(rng.normal(size=(R, L)), dtype)
    theta = jnp.asarray(rng.uniform(0.05, 1.0, R), jnp.float32)
    m_pl, r_pl = ops.topk_compress(x, theta, block=block, impl="pallas")
    m_jn, r_jn = ops.topk_compress(x, theta, block=block, impl="jnp")
    np.testing.assert_allclose(np.asarray(m_pl, np.float32),
                               np.asarray(m_jn, np.float32), atol=0, rtol=0)
    # exact identity: masked + residual == input
    np.testing.assert_allclose(
        np.asarray(m_pl, np.float32) + np.asarray(r_pl, np.float32),
        np.asarray(x, np.float32), atol=1e-6)


def test_topk_kept_fraction(rng):
    x = jnp.asarray(rng.normal(size=(2, 8192)), jnp.float32)
    for theta in [0.05, 0.1, 0.3, 0.7]:
        m, _ = ops.topk_compress(x, jnp.full((2,), theta), block=1024,
                                 impl="pallas")
        kept = float((np.asarray(m) != 0).mean())
        assert abs(kept - theta) < 0.02, (theta, kept)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24)])
def test_flash_attention_pallas_grad_vs_ref(causal, window, rng):
    """The Pallas route's custom VJP (Pallas forward in interpret mode, XLA
    backward recomputed from q/k/v) matches jax.grad of the plain
    reference: f32, |err| <= 1e-5 + 1e-4 |ref|."""
    B, S, H, KH, Dh = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KH, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KH, Dh)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(w * fn(q, k, v, causal=causal,
                                               window=window))

    g_pl = jax.grad(loss(functools.partial(ops.flash_attention,
                                           impl="pallas")),
                    argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref.attention_ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)
