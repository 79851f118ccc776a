"""Per-kernel allclose vs the pure-jnp oracle, sweeping shapes/dtypes
(interpret=True executes the kernel body on CPU)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.demosaic import demosaic_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lif_scan import lif_scan_pallas
from repro.kernels.nlm import nlm_pallas
from repro.kernels.spike_matmul import spike_matmul_pallas

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("T,N", [(3, 64), (5, 300), (8, 1025), (2, 4096)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_lif_scan(T, N, dtype):
    cur = jnp.asarray(RNG.normal(0.6, 1.0, (T, N)).astype(dtype))
    out = lif_scan_pallas(cur.astype(jnp.float32), interpret=True)
    want = ref.lif_scan_ref(cur.astype(jnp.float32))
    np.testing.assert_allclose(out, want, atol=1e-6)
    assert 0.0 < float(jnp.mean(out)) < 1.0   # neither silent nor saturated


@pytest.mark.parametrize("M,K,N", [(64, 64, 64), (100, 200, 60),
                                   (130, 257, 129)])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
def test_spike_matmul(M, K, N, density):
    x = (RNG.random((M, K)) < density).astype(np.float32)
    w = RNG.normal(0, 1, (K, N)).astype(np.float32)
    out = spike_matmul_pallas(jnp.asarray(x), jnp.asarray(w), bm=64, bk=64,
                              bn=64, interpret=True)
    np.testing.assert_allclose(out, ref.spike_matmul_ref(x, w), atol=1e-4)


@pytest.mark.parametrize("H,W", [(32, 32), (64, 96), (70, 50)])
def test_demosaic(H, W):
    raw = jnp.asarray(RNG.random((H, W)).astype(np.float32))
    out = demosaic_pallas(raw, bh=32, bw=32, interpret=True)
    np.testing.assert_allclose(out, ref.demosaic_ref(raw), atol=1e-5)


@pytest.mark.parametrize("H,W", [(32, 32), (64, 64)])
@pytest.mark.parametrize("strength", [0.1, 0.7])
def test_nlm(H, W, strength):
    img = jnp.asarray(RNG.random((H, W)).astype(np.float32))
    out = nlm_pallas(img, strength, bh=32, bw=32, interpret=True)
    np.testing.assert_allclose(out, ref.nlm_ref(img, strength), atol=1e-5)


def test_nlm_rgb_matches_ref():
    img = jnp.asarray(RNG.random((32, 32, 3)).astype(np.float32))
    out = nlm_pallas(img, 0.4, bh=32, bw=32, interpret=True)
    np.testing.assert_allclose(out, ref.nlm_ref(img, 0.4), atol=1e-5)


@pytest.mark.parametrize("BH,Sq,Sk,d", [(2, 64, 64, 16), (4, 70, 70, 32),
                                        (1, 128, 256, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(BH, Sq, Sk, d, causal):
    if not causal and Sk % 64:
        pytest.skip("non-causal needs divisible Sk")
    q = jnp.asarray(RNG.normal(0, 1, (BH, Sq, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(0, 1, (BH, Sk, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(0, 1, (BH, Sk, d)).astype(np.float32))
    out = flash_attention_pallas(q, k, v, causal=causal, bq=64, bk=64,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, want, atol=2e-4)


def test_flash_matches_model_flash_scan():
    """The Pallas kernel and the model's jnp flash-scan agree."""
    from repro.models.attention import flash_attention as model_flash
    B, S, H, hd = 2, 96, 4, 16
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(0, 1, (B, S, H, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(0, 1, (B, S, H, hd)).astype(np.float32))
    a = model_flash(q, k, v, causal=True, q_offset=0)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    b = flash_attention_pallas(qf, kf, vf, causal=True, bq=32, bk=32,
                               interpret=True)
    b = b.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(a, b, atol=2e-4)
