"""Fused SKI-TNO pipeline: parity vs the dense oracle, ragged shapes,
bf16 inputs, small-n fallbacks, and the backend autotune cache."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import ski, toeplitz
from repro.kernels import backend, ops, ref
from repro.nn.params import unbox
from tests.conftest import assert_allclose


def _setup(d=8, rank=16, m=8, seed=0, **kw):
    cfg = ski.SKIConfig(d=d, rank=rank, filter_size=m, **kw)
    params, _ = unbox(ski.ski_init(jax.random.PRNGKey(seed), cfg))
    return cfg, params


def _dense_T(params, cfg, n, causal):
    """Dense (d, n, n) oracle incl. the causal variant (masked Gram +
    causal band) — generalises ski.ski_dense_oracle."""
    r = min(cfg.rank, n)
    idx_lo, w_lo, h = ski.make_inducing(n, r)
    w = ref.dense_interp_matrix(idx_lo, w_lo, r)
    a_coef = ski.inducing_gram_coeffs(params, cfg, r, h)
    if causal:
        a_coef = toeplitz.causal_mask_coeffs(a_coef, r)
    a = toeplitz.dense_toeplitz(a_coef, r)
    t = jnp.einsum("nr,drs,ms->dnm", w, a, w)
    m = cfg.filter_size
    left = 0 if causal else m // 2
    i = jnp.arange(n)
    k_idx = (i[:, None] - i[None, :]) + left
    valid = (k_idx >= 0) & (k_idx < m)
    return t + jnp.where(valid[None], params["filt"][:, jnp.clip(k_idx, 0, m - 1)], 0.0)


# ------------------------------------------------- parity vs dense oracle
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n", [64, 100])          # 100: n % tile != 0
def test_fused_matches_dense_oracle(n, causal):
    cfg, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, n, cfg.d))
    got = ski.ski_tno_apply(params, cfg, x, causal=causal)
    want = jnp.einsum("dnm,bmd->bnd", _dense_T(params, cfg, n, causal), x)
    assert float(jnp.abs(got - want).max()) <= 1e-4


def test_bidirectional_matches_ski_dense_oracle_exact_api():
    cfg, params = _setup()
    n = 96
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n, cfg.d))
    got = ski.ski_tno_apply(params, cfg, x)
    want = jnp.einsum("dnm,bmd->bnd", ski.ski_dense_oracle(params, cfg, n), x)
    assert float(jnp.abs(got - want).max()) <= 1e-4


@pytest.mark.parametrize("causal", [False, True])
def test_fused_matches_unfused_pipeline(causal):
    """Fused two-pass (direct Gram matmul, hat W) == unfused 4-kernel
    pipeline (FFT Gram, scatter W) — two independent computation routes."""
    cfg, params = _setup(d=6, rank=9, m=4)
    cfg_u = ski.SKIConfig(d=6, rank=9, filter_size=4, fused=False)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 75, 6))  # odd n
    assert_allclose(ski.ski_tno_apply(params, cfg, x, causal=causal),
                    ski.ski_tno_apply(params, cfg_u, x, causal=causal),
                    rtol=1e-4, atol=1e-4)


def test_fused_bf16_input_fp32_accumulation():
    cfg, params = _setup()
    n = 128
    x32 = jax.random.normal(jax.random.PRNGKey(4), (1, n, cfg.d))
    x16 = x32.astype(jnp.bfloat16)
    got = ski.ski_tno_apply(params, cfg, x16)
    assert got.dtype == jnp.bfloat16
    want = ski.ski_tno_apply(params, cfg, x32)
    assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_plan_reuse_is_equivalent():
    cfg, params = _setup()
    n = 80
    x = jax.random.normal(jax.random.PRNGKey(5), (1, n, cfg.d))
    plan = ski.ski_plan(params, cfg, n, causal=False)
    assert "a_dense" in plan                       # fused-eligible
    assert_allclose(ski.ski_tno_apply(params, cfg, x, plan=plan),
                    ski.ski_tno_apply(params, cfg, x))


def test_stale_plan_is_rejected():
    """A plan built with the wrong causal flag or n computes a different
    operator — must raise, not silently return wrong numbers."""
    cfg, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 80, cfg.d))
    plan = ski.ski_plan(params, cfg, 80, causal=False)
    with pytest.raises(ValueError, match="plan mismatch"):
        ski.ski_tno_apply(params, cfg, x, causal=True, plan=plan)
    with pytest.raises(ValueError, match="plan mismatch"):
        ski.ski_tno_apply(params, cfg, x[:, :64], plan=plan)


# ------------------------------------------------------ Pallas fused path
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,n,d,r,m", [
    (1, 128, 128, 16, 8),
    (1, 100, 136, 17, 8),     # ragged n and d (pad + slice path)
])
def test_fused_pass2_pallas_matches_ref(b, n, d, r, m, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (b, n, d)).astype(dtype)
    z = jax.random.normal(jax.random.PRNGKey(1), (b, r, d)).astype(dtype)
    a = jax.random.normal(jax.random.PRNGKey(2), (d, r, r))
    filt = jax.random.normal(jax.random.PRNGKey(3), (d, m)).astype(dtype)
    got = ops.ski_fused_pass2(x, z, a, filt, False, use_pallas=True)
    want = ref.ski_fused_pass2_ref(x, z, a, filt, False)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    assert_allclose(got, want, rtol=tol, atol=tol)


def test_short_conv_pallas_ragged_and_small_n():
    # ragged n, d -> pad/slice path (old code asserted n % bn == 0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 300, 136))
    filt = jax.random.normal(jax.random.PRNGKey(1), (136, 8))
    for causal in (True, False):
        assert_allclose(ops.short_conv(x, filt, causal, use_pallas=True),
                        ref.short_conv_ref(x, filt, causal),
                        rtol=5e-4, atol=5e-4)
    # n < m (bn=8 < m=16): falls back to the reference path, no crash
    xs = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 16))
    fs = jax.random.normal(jax.random.PRNGKey(3), (16, 16))
    assert_allclose(ops.short_conv(xs, fs, True, use_pallas=True),
                    ref.short_conv_ref(xs, fs, True))
    # same fallback in the fused pass-2 kernel
    zs = jax.random.normal(jax.random.PRNGKey(4), (1, 3, 16))
    a = jax.random.normal(jax.random.PRNGKey(5), (16, 3, 3))
    assert_allclose(ops.ski_fused_pass2(xs, zs, a, fs, True, use_pallas=True),
                    ref.ski_fused_pass2_ref(xs, zs, a, fs, True),
                    rtol=5e-4, atol=5e-4)


def test_interp_pallas_ragged_shapes():
    n, d, r = 300, 136, 17
    x = jax.random.normal(jax.random.PRNGKey(0), (1, n, d))
    idx_lo, w_lo, h = ski.make_inducing(n, r)
    assert_allclose(ops.interp_reduce(x, idx_lo, w_lo, r, use_pallas=True),
                    ref.interp_reduce_ref(x, idx_lo, w_lo, r),
                    rtol=1e-3, atol=1e-3)
    z = jax.random.normal(jax.random.PRNGKey(1), (1, r, d))
    assert_allclose(ops.interp_expand(z, idx_lo, w_lo, use_pallas=True),
                    ref.interp_expand_ref(z, idx_lo, w_lo),
                    rtol=1e-3, atol=1e-3)


# ------------------------------------------------------- backend subsystem
def test_backend_fit_block_bounds_padding():
    for size in (7, 100, 300, 2048, 5000):
        blk = backend.fit_block(size, 256)
        assert blk % 8 == 0 and blk <= max(256, backend.round_up(size, 8))
        assert backend.round_up(size, blk) - size < blk  # waste < one tile


def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    backend.clear_cache(memory_only=True)
    calls = []
    tune = lambda bn, bd: calls.append((bn, bd)) or jnp.zeros(())
    blocks = backend.get_blocks("short_conv", 96, 16, jnp.float32, True,
                                tune_call=tune)
    n_swept = len(calls)
    assert n_swept > 1                         # swept several candidates
    assert (tmp_path / "tune.json").exists()   # persisted
    backend.clear_cache(memory_only=True)      # force re-read from disk
    again = backend.get_blocks("short_conv", 96, 16, jnp.float32, True,
                               tune_call=tune)
    assert again == blocks and len(calls) == n_swept  # cache hit: no sweep
    monkeypatch.delenv("REPRO_AUTOTUNE")
    backend.clear_cache(memory_only=True)


def test_autotune_raises_when_every_candidate_fails(tmp_path, monkeypatch):
    """A sweep in which no candidate runs must not fall back to the
    heuristic in silence: the kernel does not run on this device."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    backend.clear_cache(memory_only=True)

    def tune(bn, bd):
        raise ValueError(f"refused ({bn}, {bd})")
    try:
        with pytest.raises(RuntimeError, match="every candidate failed"):
            backend.get_blocks("short_conv", 96, 16, jnp.float32, True,
                               tune_call=tune)
    finally:
        backend.clear_cache(memory_only=True)
    assert not (tmp_path / "tune.json").exists()


def test_dispatch_policy_env(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "auto")
    assert backend.use_pallas_default() == (backend.platform() == "tpu")
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    assert backend.use_pallas_default() is True
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    assert backend.use_pallas_default() is False
    assert backend.resolve_use_pallas(True) is True
    ops.set_default_backend(True)
    try:
        monkeypatch.setenv("REPRO_USE_PALLAS", "0")
        assert backend.use_pallas_default() is True   # programmatic wins
    finally:
        ops.set_default_backend(None)
