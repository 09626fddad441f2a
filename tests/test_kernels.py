"""Pallas kernels vs jnp oracles — shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models.layers import chunked_attention

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # B, S, T, H, K, hd, causal, window, bq, bk
    (2, 128, 128, 4, 2, 64, True, 0, 64, 64),
    (1, 256, 256, 8, 8, 64, True, 0, 128, 128),
    (2, 128, 128, 4, 1, 32, False, 0, 64, 64),
    (1, 256, 256, 4, 2, 64, True, 64, 64, 64),
    (2, 96, 200, 4, 4, 128, False, 0, 64, 128),  # uneven, cross
    (1, 64, 64, 2, 2, 256, True, 0, 64, 64),  # big head dim
]


@pytest.mark.parametrize("case", ATTN_CASES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    B, S, T, H, K, hd, causal, window, bq, bk = case
    q = jax.random.normal(KEY, (B, S, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, K, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, K, hd), dtype)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=bq, block_kv=bk, interpret=True
    )
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


def test_chunked_xla_attention_matches_oracle_with_kvlen_and_offset():
    B, S, T, H, K, hd = 2, 24, 64, 4, 2, 16
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, K, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, K, hd))
    out = chunked_attention(
        q, k, v, q_offset=8, kv_len=jnp.int32(30), causal=True, kv_chunk=16
    )
    want = ref.attention_ref(q, k, v, q_offset=8, kv_len=jnp.int32(30), causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_unrolled_causal_attention_matches_scan():
    B, S, H, K, hd = 1, 128, 4, 2, 32
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, K, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, K, hd))
    a = chunked_attention(q, k, v, causal=True, kv_chunk=32, unroll_causal=True)
    b = chunked_attention(q, k, v, causal=True, kv_chunk=32, unroll_causal=False)
    np.testing.assert_allclose(a, b, atol=1e-6)


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------

SSD_CASES = [
    # B, S, nh, hp, ng, ds, chunk
    (2, 128, 4, 16, 1, 32, 32),
    (1, 256, 8, 64, 2, 64, 64),
    (2, 64, 4, 32, 4, 16, 16),
    (1, 128, 2, 8, 1, 8, 128),  # single chunk
]


def _ssd_inputs(B, S, nh, hp, ng, ds, dtype=jnp.float32):
    ks = [jax.random.fold_in(KEY, i) for i in range(6)]
    x = jax.random.normal(ks[0], (B, S, nh, hp), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, ng, ds)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, ng, ds)) * 0.3).astype(dtype)
    D = jax.random.normal(ks[5], (nh,))
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("case", SSD_CASES, ids=str)
def test_ssd_chunked_ref_matches_naive(case):
    B, S, nh, hp, ng, ds, chunk = case
    x, dt, A, Bm, Cm, D = _ssd_inputs(B, S, nh, hp, ng, ds)
    want = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    got = ref.ssd_chunked_ref(x, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("case", SSD_CASES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_matches_naive(case, dtype):
    B, S, nh, hp, ng, ds, chunk = case
    x, dt, A, Bm, Cm, D = _ssd_inputs(B, S, nh, hp, ng, ds, dtype)
    want = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    got, st = ssd_scan_pallas(
        x, dt, A, Bm, Cm, D, chunk=chunk, return_state=True, interpret=True
    )
    # naive oracle accumulates differently (O(S^2) sum order): 2e-4 at f32
    tol = _tol(dtype) if dtype == jnp.bfloat16 else dict(atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **tol
    )
    # final state matches the chunked reference's
    _, st_ref = ref.ssd_chunked_ref(
        x, dt, A, Bm, Cm, D, chunk=chunk, return_state=True
    )
    np.testing.assert_allclose(st, st_ref, atol=2e-2 if dtype == jnp.bfloat16 else 2e-5)


def test_ssd_decode_steps_match_full_scan():
    B, S, nh, hp, ng, ds = 1, 16, 2, 8, 1, 8
    x, dt, A, Bm, Cm, D = _ssd_inputs(B, S, nh, hp, ng, ds)
    y_full = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    st = jnp.zeros((B, nh, ds, hp))
    for t in range(S):
        y_t, st = ref.ssd_decode_step(st, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        np.testing.assert_allclose(y_t, y_full[:, t], atol=1e-5)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

RGLRU_CASES = [
    # B, S, W, bt, bc
    (2, 128, 64, 32, 64),
    (1, 100, 200, 64, 128),  # uneven both dims
    (2, 64, 256, 64, 128),
    (1, 32, 16, 32, 16),
]


def _rglru_inputs(B, S, W, dtype=jnp.float32):
    ks = [jax.random.fold_in(KEY, 20 + i) for i in range(4)]
    return (
        jax.random.normal(ks[0], (B, S, W), dtype),
        jax.random.normal(ks[1], (B, S, W), dtype),
        jax.random.normal(ks[2], (B, S, W), dtype),
        jax.random.normal(ks[3], (W,)),
    )


@pytest.mark.parametrize("case", RGLRU_CASES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_pallas_matches_ref(case, dtype):
    B, S, W, bt, bc = case
    x, r, i, lam = _rglru_inputs(B, S, W, dtype)
    want, st_want = ref.rglru_ref(x, r, i, lam, return_state=True)
    got, st = rglru_scan_pallas(
        x, r, i, lam, block_t=bt, block_c=bc, return_state=True, interpret=True
    )
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(
        st, st_want, atol=2e-2 if dtype == jnp.bfloat16 else 2e-5
    )


def test_rglru_decode_steps_match_full_scan():
    B, S, W = 1, 12, 16
    x, r, i, lam = _rglru_inputs(B, S, W)
    y_full = ref.rglru_ref(x, r, i, lam)
    st = jnp.zeros((B, W))
    for t in range(S):
        y_t, st = ref.rglru_decode_step(st, x[:, t], r[:, t], i[:, t], lam)
        np.testing.assert_allclose(y_t, y_full[:, t], atol=1e-5)


def test_rglru_stability_long_sequence():
    """Decay in (0,1): the state never blows up over 4k steps."""
    B, S, W = 1, 4096, 8
    x, r, i, lam = _rglru_inputs(B, S, W)
    y = ref.rglru_ref(x, r, i, lam)
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y).max()) < 1e3


# ---------------------------------------------------------------------------
# Alg-2 placement sweep (scheduler hot path)
# ---------------------------------------------------------------------------


def _placement_block(B=257, n_t=6, n_f=5, seed=0):
    rng = np.random.default_rng(seed)
    t_slr = rng.uniform(30.0, 120.0, n_f)
    t_cfg = rng.uniform(0.0, 8.0, n_f)
    iis = rng.uniform(0.0, 6.0, n_t)
    # Rows spread around the fleet capacity: mixed feasible/infeasible.
    shares = rng.uniform(0.5, 1.5, (B, n_t)) * (
        rng.uniform(0.3, 1.3, (B, 1)) * t_slr.sum() / n_t
    )
    return shares, iis, t_slr, t_cfg


@pytest.mark.parametrize("block_rows", [128, 1024], ids=["tiled", "one-tile"])
@pytest.mark.parametrize("repay_init", [True, False], ids=["padpsfr", "preemptive"])
def test_placement_sweep_pallas_matches_ref(block_rows, repay_init):
    from repro.core.placement_backends.jax_runtime import x64
    from repro.kernels.placement_step import placement_sweep_pallas

    shares, iis, t_slr, t_cfg = _placement_block()
    resume = 0.0 if repay_init else 9.5
    with x64():
        want = ref.placement_sweep_ref(
            jnp.asarray(shares), jnp.asarray(iis), jnp.asarray(t_slr),
            jnp.asarray(t_cfg), jnp.float64(resume), repay_init=repay_init,
        )
        got = placement_sweep_pallas(
            jnp.asarray(shares), jnp.asarray(iis), jnp.asarray(t_slr),
            jnp.asarray(t_cfg), resume_cost=resume, repay_init=repay_init,
            block_rows=block_rows, interpret=True,
        )
    assert int(np.asarray(want[0]).sum()) > 0  # the block exercises both verdicts
    assert int((~np.asarray(want[0])).sum()) > 0
    for g, w, name in zip(got, want, ("feasible", "placed", "n_splits", "devices"), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


def test_placement_sweep_ref_matches_numpy_backend():
    """The jnp reference is pinned to the core numpy engine bit-for-bit."""
    from repro.core.placement_backends import get_backend
    from repro.core.placement_backends.jax_runtime import x64

    shares, iis, t_slr, t_cfg = _placement_block(B=123, seed=3)
    bn = get_backend("numpy").place_block(shares, iis, t_slr, t_cfg)
    with x64():
        feas, placed, n_splits, dev = ref.placement_sweep_ref(
            jnp.asarray(shares), jnp.asarray(iis), jnp.asarray(t_slr),
            jnp.asarray(t_cfg), jnp.float64(0.0),
        )
    np.testing.assert_array_equal(np.asarray(feas), bn.feasible)
    np.testing.assert_array_equal(np.asarray(placed), bn.placed_tasks)
    np.testing.assert_array_equal(np.asarray(n_splits), bn.n_splits)
    np.testing.assert_array_equal(np.asarray(dev), bn.devices_used)
