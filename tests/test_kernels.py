"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the brief's per-kernel allclose requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: deterministic fallback sweeps instead
    from _hypothesis_shim import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_bhsd_ref
from repro.kernels.quantize.ops import dequantize_int8, quantize_int8
from repro.kernels.quantize.quantize import quantize_int8_2d
from repro.kernels.quantize.ref import quantize_int8_2d_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_bhsp
from repro.kernels.ssd_scan.ref import ssd_scan_bhsp_ref


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kv,d,block",
    [
        (1, 128, 2, 2, 32, 64),   # MHA
        (2, 256, 4, 2, 64, 128),  # GQA 2:1
        (1, 192, 6, 2, 16, 64),   # seq not a multiple of the block (pad path)
        (1, 128, 8, 1, 32, 64),   # MQA
    ],
)
def test_flash_attention_sweep(dtype, b, s, h, kv, d, block):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    ref = attention_bhsd_ref(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        q_per_kv=h // kv, causal=True, scale=d ** -0.5,
    )
    ref = jnp.moveaxis(ref, 1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


@pytest.mark.parametrize("window", [32, 64, 200])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, s, h, kv, d = 1, 256, 2, 2, 32
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64)
    ref = attention_bhsd_ref(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        q_per_kv=1, causal=True, window=window, scale=d ** -0.5,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jnp.moveaxis(ref, 1, 2)), atol=2e-5
    )


# ------------------------------------------------------------------- SSD scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,s,p,n,q",
    [(1, 2, 64, 16, 16, 16), (2, 3, 128, 16, 32, 32), (1, 4, 256, 32, 64, 64)],
)
def test_ssd_scan_sweep(dtype, b, h, s, p, n, q):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, h, s, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, n), dtype)
    cm = jax.random.normal(ks[4], (b, s, n), dtype)
    yk, sk = ssd_scan_bhsp(x, dt, a, bm, cm, chunk=q, interpret=True)
    yr, sr = ssd_scan_bhsp_ref(x, dt, a, bm, cm, chunk=q)
    tol = 1e-3 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), atol=tol, rtol=tol)


def test_ssd_state_continuity():
    """Final state from the kernel == running the recurrence token by token."""
    b, h, s, p, n, q = 1, 1, 64, 8, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, h, s, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    _, s_kernel = ssd_scan_bhsp(x, dt, a, bm, cm, chunk=q, interpret=True)
    state = np.zeros((p, n))
    for t in range(s):
        da = float(dt[0, 0, t]) * float(a[0])
        state = state * np.exp(da) + float(dt[0, 0, t]) * np.outer(
            np.asarray(x[0, 0, t]), np.asarray(bm[0, t])
        )
    np.testing.assert_allclose(np.asarray(s_kernel[0, 0]), state, atol=1e-3)


# ------------------------------------------------------------------- quantize
@given(
    n=st.integers(1, 4000),
    scale=st.floats(1e-3, 1e3),
    seed=st.integers(0, 100),
)
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_error_bound(n, scale, seed):
    """|x - dq(q(x))| <= absmax/127/2 + eps per block, any shape."""
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(seed), (n,)) * scale, np.float32
    )
    q, s = quantize_int8(jnp.asarray(x))
    xr = np.asarray(dequantize_int8(q, s))
    bound = np.abs(x).max() / 127.0 * 0.5001 + 1e-6
    assert np.abs(xr - x).max() <= bound * 1.01 + 1e-6


@pytest.mark.parametrize("rows,block", [(8, 256), (16, 128), (8, 512)])
def test_quantize_kernel_matches_ref(rows, block):
    x = jax.random.normal(jax.random.PRNGKey(4), (rows * 4, block)) * 10
    qk, sk = quantize_int8_2d(x, block=block, rows=rows, interpret=True)
    qr, sr = quantize_int8_2d_ref(x)
    assert np.array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)


def test_quantize_zero_block():
    x = jnp.zeros((8, 256))
    q, s = quantize_int8_2d(x, interpret=True)
    assert np.all(np.asarray(q) == 0)
    xr = dequantize_int8(q.reshape(-1), s[:, 0])
    assert np.all(np.asarray(xr) == 0)


# ------------------------------------------------------------------ waterfill
def _waterfill_case(seed, *, with_edges):
    """A padded max-min scenario: nc live lanes scattered across ncp slots,
    junk caps/indices in the dead lanes (the mask must neutralize them)."""
    rng = np.random.default_rng(seed)
    nv = int(rng.integers(2, 10))
    nc = int(rng.integers(1, 40))
    ncp = nc + int(rng.integers(0, 17))
    active = np.zeros(ncp, dtype=bool)
    active[rng.permutation(ncp)[:nc]] = True
    caps = np.where(active, rng.uniform(0.5, 8.0, ncp), 123.0)
    src = rng.integers(0, nv, ncp)
    dst = rng.integers(0, nv, ncp)
    eg = rng.uniform(1.0, 12.0, nv)
    inn = rng.uniform(1.0, 12.0, nv)
    if with_edges:
        ne = int(rng.integers(1, 5))
        eid = rng.integers(0, ne, ncp)
        ed = rng.uniform(2.0, 20.0, ne)
    else:
        ne, eid, ed = 0, np.zeros(ncp, dtype=np.int64), None
    return caps, src, dst, eg, inn, eid, ed, active, nv, ne


@pytest.mark.parametrize("with_edges", [False, True])
@pytest.mark.parametrize("seed", range(4))
def test_masked_waterfill_bitwise_vs_flowsim_oracle(seed, with_edges):
    """ref.masked_maxmin_rates on padded lanes is BITWISE the flowsim
    numpy water-filler on the compacted set (the f64 parity contract the
    jax sim engine stands on), and dead lanes come back exactly 0.0."""
    from repro.kernels.waterfill.ref import masked_maxmin_rates
    from repro.transfer.flowsim import _maxmin_rates_arr

    caps, src, dst, eg, inn, eid, ed, active, nv, ne = _waterfill_case(
        seed, with_edges=with_edges,
    )
    want = _maxmin_rates_arr(
        caps[active], src[active], dst[active], eg, inn,
        eid[active] if ed is not None else None, ed,
    )
    with jax.enable_x64(True):
        got = np.asarray(masked_maxmin_rates(
            jnp.asarray(caps), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(eg), jnp.asarray(inn), jnp.asarray(eid),
            None if ed is None else jnp.asarray(ed),
            jnp.asarray(active), n_vms=nv, n_edges=ne,
        ))
    assert np.array_equal(got[active], want)
    assert np.all(got[~active] == 0.0)


@pytest.mark.parametrize("with_edges", [False, True])
@pytest.mark.parametrize("seed", range(3))
def test_pallas_waterfill_matches_oracle_f32(seed, with_edges):
    """The Pallas one-hot-matmul kernel (interpret mode off-TPU) tracks the
    f64 oracle to f32 tolerance, masked lanes included."""
    from repro.kernels.waterfill.ops import waterfill_rates
    from repro.transfer.flowsim import _maxmin_rates_arr

    caps, src, dst, eg, inn, eid, ed, active, nv, ne = _waterfill_case(
        seed, with_edges=with_edges,
    )
    want = _maxmin_rates_arr(
        caps[active], src[active], dst[active], eg, inn,
        eid[active] if ed is not None else None, ed,
    )
    got = np.asarray(waterfill_rates(
        caps, src, dst, eg, inn,
        eid if ed is not None else None, ed, active,
    ))
    np.testing.assert_allclose(got[active], want, rtol=5e-3, atol=5e-3)
    assert np.all(got[~active] == 0.0)
