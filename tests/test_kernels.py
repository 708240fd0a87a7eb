"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles,
run in Pallas interpret mode on CPU (the kernels target TPU; their
compiles for a described v5e are in test_chip_compile.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 128, 2, 32),     # exactly one tile
    (2, 200, 4, 32),     # ragged seq
    (1, 384, 8, 64),     # multi-tile, GQA 8:2
])
@pytest.mark.parametrize("mode", ["causal", "full", "window"])
def test_flash_attention_sweep(shape, dtype, mode):
    from repro.kernels.flash_attention import ops
    B, S, H, hd = shape
    KV = max(1, H // 2)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, hd), dtype)
    k = jnp.asarray(rng.randn(B, S, KV, hd), dtype)
    v = jnp.asarray(rng.randn(B, S, KV, hd), dtype)
    kwargs = {"causal": dict(causal=True),
              "full": dict(causal=False),
              "window": dict(causal=True, window=37)}[mode]
    out = ops.flash_attention(q, k, v, interpret=True, **kwargs)
    ref = ops.flash_attention_reference(q, k, v, **kwargs)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_cross_lengths():
    from repro.kernels.flash_attention import ops
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 300, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 300, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, interpret=True)
    ref = ops.flash_attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ----------------------------------------------------------------------
# ssd scan
# ----------------------------------------------------------------------

@pytest.mark.parametrize("dims", [
    (1, 32, 2, 8, 8, 16),
    (2, 64, 3, 16, 8, 16),
    (1, 128, 4, 32, 16, 32),
])
def test_ssd_scan_sweep(dims):
    from repro.kernels.ssd_scan import ops
    from repro.models.ssm import ssd_chunked_ref
    B, S, H, P, N, chunk = dims
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, H, P), jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(B, S, H)) * 0.1 + 0.01, jnp.float32)
    a = -jnp.asarray(np.abs(rng.randn(H)) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(B, S, 1, N), jnp.float32)
    c = jnp.asarray(rng.randn(B, S, 1, N), jnp.float32)
    y_k, h_k = ops.ssd_chunked(x, dt, a, b, c, chunk, interpret=True)
    y_r, h_r = ssd_chunked_ref(x, dt, a, b, c, chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               atol=1e-4, rtol=1e-4)


def test_ssd_scan_matches_sequential_recurrence():
    """SSD chunked == naive per-token state recurrence (the SSM definition)."""
    from repro.models.ssm import ssd_chunked_ref
    rng = np.random.RandomState(2)
    B, S, H, P, N = 1, 32, 2, 8, 4
    x = rng.randn(B, S, H, P).astype(np.float32)
    dt = (np.abs(rng.randn(B, S, H)) * 0.1 + 0.01).astype(np.float32)
    a = -(np.abs(rng.randn(H)) + 0.5).astype(np.float32)
    b = rng.randn(B, S, 1, N).astype(np.float32)
    c = rng.randn(B, S, 1, N).astype(np.float32)
    y, hf = ssd_chunked_ref(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a),
                            jnp.asarray(b), jnp.asarray(c), chunk=8)
    # naive recurrence
    h = np.zeros((B, H, N, P))
    ys = np.zeros((B, S, H, P))
    for t in range(S):
        for hh in range(H):
            da = dt[:, t, hh] * a[hh]
            h[:, hh] = h[:, hh] * np.exp(da)[:, None, None] + \
                dt[:, t, hh][:, None, None] * np.einsum(
                    "bn,bp->bnp", b[:, t, 0], x[:, t, hh])
            ys[:, t, hh] = np.einsum("bn,bnp->bp", c[:, t, 0], h[:, hh])
    np.testing.assert_allclose(np.asarray(y), ys, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(hf), h, atol=1e-3, rtol=1e-3)


# ----------------------------------------------------------------------
# quant
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n", [1024, 3000, 1 << 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_roundtrip_sweep(n, dtype):
    from repro.kernels.quant import ops
    from repro.kernels.quant.ref import quantize_ref
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n) * 3, dtype)
    q, s = ops.quantize(x, interpret=True)
    qr, sr = quantize_ref(x)
    # allow ±1 code at exact rounding ties (kernel fuses the divide)
    assert np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32)).max() <= 1
    xd = ops.dequantize(q, s, (n,), dtype, interpret=True)
    err = np.abs(np.asarray(xd, np.float32) - np.asarray(x, np.float32)).max()
    scale_bound = float(np.asarray(s).max())
    # bf16 output adds its own rounding (8-bit mantissa) on top of the
    # int8 quantization step
    out_eps = (2.0 ** -8) * float(np.abs(np.asarray(x, np.float32)).max()) \
        if dtype == jnp.bfloat16 else 0.0
    assert err <= scale_bound * 0.51 + out_eps + 1e-6


def test_quant_property_scale_bound():
    """Property: |dequant(quant(x)) - x| <= scale/2 per block, any input."""
    from helpers import require_hypothesis
    require_hypothesis()
    from hypothesis import given, settings, strategies as st
    from repro.kernels.quant.ref import quantize_ref, dequantize_ref

    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                    min_size=1, max_size=300))
    def check(vals):
        x = jnp.asarray(np.array(vals, np.float32))
        q, s = quantize_ref(x)
        xd = dequantize_ref(q, s, x.shape, jnp.float32)
        bound = np.repeat(np.asarray(s)[:, 0], 1024)[: x.size] * 0.5 + 1e-5
        assert (np.abs(np.asarray(xd) - np.asarray(x)) <= bound).all()

    check()


# ----------------------------------------------------------------------
# swe step
# ----------------------------------------------------------------------

@pytest.mark.parametrize("E", [100, 512, 1300])
def test_swe_step_sweep(E):
    from repro.kernels.swe_step import ops
    from repro.kernels.swe_step.ref import swe_step_ref
    rng = np.random.RandomState(E)
    u = jnp.asarray(np.abs(rng.randn(E, 3)) * 0.1 + np.array([1.0, 0, 0]),
                    jnp.float32)
    u_n = jnp.asarray(np.abs(rng.randn(E, 3, 3)) * 0.1 + np.array([1.0, 0, 0]),
                      jnp.float32)
    nx = jnp.asarray(rng.randn(E, 3) * 0.01, jnp.float32)
    ny = jnp.asarray(rng.randn(E, 3) * 0.01, jnp.float32)
    et = jnp.asarray(rng.randint(0, 3, (E, 3)), jnp.int32)
    area = jnp.asarray(np.abs(rng.randn(E)) * 1e-3 + 1e-4, jnp.float32)
    valid = jnp.asarray((rng.rand(E) > 0.05).astype(np.float32))
    out = ops.swe_step(u, u_n, nx, ny, et, area, valid, 1.0, dt=1e-4,
                       interpret=True)
    ref = swe_step_ref(u, u_n, nx, ny, et, area, valid, 1.0, dt=1e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
