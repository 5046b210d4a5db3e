"""Causal FD-TNO fused pipeline (kernels/fd_fused.py): oracle parity for
each Pallas kernel, fwd + grad parity of the differentiable op against the
jnp reference (interpret mode, the SKI grad-parity tiers: fp32 ≤ 1e-5,
bf16 ≤ 2e-2), exact causality of the realised operator, and the
no-silent-fallback counter contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fd as fd_mod
from repro.core.hilbert import causal_spectrum
from repro.kernels import backend, fd_fused, ops, ref
from repro.nn.params import unbox

GRAD_TOL = {jnp.dtype(jnp.float32): 1e-5, jnp.dtype(jnp.bfloat16): 2e-2}


def _rel(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-12))


@pytest.fixture(autouse=True)
def _reset_counters():
    fd_fused.reset_counters()
    yield


# ------------------------------------------------------ kernel vs oracle
@pytest.mark.parametrize("d,n", [(8, 16), (5, 33), (12, 64), (3, 7)])
def test_hilbert_window_matches_ref(d, n):
    kt = jax.random.normal(jax.random.PRNGKey(n), (d, 2 * n))
    got = fd_fused.hilbert_window_pallas(kt, n, interpret=True)
    want = ref.hilbert_window_ref(kt, n)
    assert _rel(got, want) <= 1e-6
    # window zeroes the negative lags exactly (t > n)
    assert float(jnp.abs(got[:, n + 1:]).max()) == 0.0


def test_hilbert_window_grad_is_window():
    """Diagonal window ⇒ the VJP is the same window applied to the
    cotangent (self-adjoint)."""
    d, n = 4, 12
    kt = jax.random.normal(jax.random.PRNGKey(0), (d, 2 * n))
    g = jax.random.normal(jax.random.PRNGKey(1), (d, 2 * n))
    _, vjp = jax.vjp(
        lambda k: fd_fused.hilbert_window_pallas(k, n, interpret=True), kt)
    (dk,) = vjp(g)
    assert _rel(dk, ref.hilbert_window_ref(g, n)) <= 1e-6


@pytest.mark.parametrize("b,f,d", [(2, 17, 8), (1, 65, 12), (3, 9, 3)])
def test_spectral_multiply_matches_ref(b, f, d):
    ks = jax.random.split(jax.random.PRNGKey(f), 4)
    xr, xi = (jax.random.normal(ks[i], (b, f, d)) for i in range(2))
    kr, ki = (jax.random.normal(ks[2 + i], (f, d)) for i in range(2))
    yr, yi = fd_fused.fd_spectral_multiply_pallas(xr, xi, kr, ki,
                                                  interpret=True)
    wr, wi = ref.fd_spectral_multiply_ref(xr, xi, kr, ki)
    assert _rel(yr, wr) <= 1e-6 and _rel(yi, wi) <= 1e-6


@pytest.mark.parametrize("b,f,d", [(2, 17, 8), (4, 33, 5)])
def test_khat_grad_matches_ref(b, f, d):
    ks = jax.random.split(jax.random.PRNGKey(b * f), 4)
    gr, gi, xr, xi = (jax.random.normal(k, (b, f, d)) for k in ks)
    dr, di = fd_fused.fd_khat_grad_pallas(gr, gi, xr, xi, interpret=True)
    wr, wi = ref.fd_khat_grad_ref(gr, gi, xr, xi)
    assert _rel(dr, wr) <= 1e-6 and _rel(di, wi) <= 1e-6


# ---------------------------------------------------- op fwd/grad parity
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,n,d", [(2, 32, 8), (1, 33, 5), (2, 64, 16)])
def test_fd_tno_fwd_matches_oracle(b, n, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (b, n, d)).astype(dtype)
    khat = jax.random.normal(jax.random.PRNGKey(1), (d, n + 1)).astype(dtype)
    got = ops.fd_tno(x, khat, use_pallas=True, interpret=True)
    want = ref.fd_tno_ref(x, khat)
    assert got.dtype == dtype
    assert _rel(got, want) <= GRAD_TOL[jnp.dtype(dtype)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,n,d", [(2, 32, 8), (1, 17, 5)])
def test_fd_tno_grad_matches_oracle(b, n, d, dtype):
    """jax.grad through the Pallas op (kernel backward: conjugated-spectrum
    multiply + khat reduction) vs plain autodiff of the jnp oracle."""
    x = jax.random.normal(jax.random.PRNGKey(2), (b, n, d)).astype(dtype)
    khat = jax.random.normal(jax.random.PRNGKey(3), (d, n + 1)).astype(dtype)

    def loss(fn):
        return lambda x_, k_: jnp.sum(jnp.sin(fn(x_, k_).astype(jnp.float32)))

    g_pl = jax.grad(loss(lambda x_, k_: ops.fd_tno(
        x_, k_, use_pallas=True, interpret=True)), argnums=(0, 1))(x, khat)
    g_rf = jax.grad(loss(ref.fd_tno_ref), argnums=(0, 1))(x, khat)
    tol = GRAD_TOL[jnp.dtype(dtype)]
    assert _rel(g_pl[0], g_rf[0]) <= tol, "dx mismatch"
    assert _rel(g_pl[1], g_rf[1]) <= tol, "dkhat mismatch"
    # no-silent-fallback contract: the differentiated forward and the
    # kernel backward both ran, the reference backward did not
    assert fd_fused.counters["fwd"] == 1
    assert fd_fused.counters["bwd_kernel"] == 1
    assert fd_fused.counters["bwd_ref"] == 0


def test_fd_tno_grad_ref_escape_hatch():
    """REPRO_PALLAS_GRAD=0 keeps the Pallas forward but swaps the backward
    to the jnp reference formulas — and the counters record it."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 4))
    khat = jax.random.normal(jax.random.PRNGKey(5), (4, 17))
    backend.set_default_pallas_grad(False)
    try:
        g = jax.grad(lambda x_: jnp.sum(
            ops.fd_tno(x_, khat, use_pallas=True, interpret=True)))(x)
    finally:
        backend.set_default_pallas_grad(None)
    g_want = jax.grad(lambda x_: jnp.sum(ref.fd_tno_ref(x_, khat)))(x)
    assert _rel(g, g_want) <= 1e-5
    assert fd_fused.counters["bwd_ref"] == 1
    assert fd_fused.counters["bwd_kernel"] == 0


def test_ops_dispatch_ref_path_leaves_counters():
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 4))
    khat = jax.random.normal(jax.random.PRNGKey(7), (4, 9))
    y = ops.fd_tno(x, khat, use_pallas=False)
    assert _rel(y, ref.fd_tno_ref(x, khat)) == 0.0
    assert fd_fused.counters["fwd"] == 0


# ------------------------------------------------------------- causality
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [16, 33])
def test_fd_tno_operator_is_exactly_causal(n, dtype):
    """An impulse at position t0 must produce nothing before t0 (within
    dtype eps — the analytic window zeroes negative lags exactly, not to
    FFT-leakage level)."""
    d, t0 = 6, n // 2
    khat = jax.random.normal(jax.random.PRNGKey(n), (d, n + 1)).astype(dtype)
    x = jnp.zeros((1, n, d), dtype).at[0, t0, :].set(1.0)
    y = np.asarray(ops.fd_tno(x, khat, use_pallas=True, interpret=True),
                   np.float32)
    scale = max(float(np.abs(y).max()), 1.0)
    eps = 1e-5 if dtype == jnp.float32 else 1e-2
    assert np.abs(y[0, :t0]).max() <= eps * scale


@pytest.mark.parametrize("n", [16, 31])
def test_realised_time_kernel_is_exactly_causal(n):
    """The time kernel the op realises — irfft of its causal spectrum —
    vanishes on negative lags (k[τ<0] ≡ 0 within dtype eps)."""
    d = 4
    khat = jax.random.normal(jax.random.PRNGKey(n), (d, n + 1))
    kr, ki = fd_fused.causal_khat_planes(khat, interpret=True)
    k_time = np.asarray(jnp.fft.irfft((kr + 1j * ki).T, n=2 * n, axis=-1))
    scale = max(float(np.abs(k_time).max()), 1.0)
    assert np.abs(k_time[:, n + 1:]).max() <= 1e-5 * scale
    # and it agrees with the hilbert-module construction
    spec = np.asarray(causal_spectrum(khat))
    np.testing.assert_allclose(np.asarray(kr), spec.T.real, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ki), spec.T.imag, rtol=1e-5,
                               atol=1e-5)


# -------------------------------------------------- core/fd integration
def test_fd_tno_apply_routes_causal_through_op():
    """core.fd.fd_tno_apply (causal) == the legacy complex-multiply path,
    and the plan carries khat_real for the fused op."""
    from repro.core.tno import TNOConfig, tno_init, tno_plan, tno_apply
    cfg = fd_mod.FDConfig(d=6, causal=True)
    params, _ = unbox(fd_mod.fd_init(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 6))
    y = fd_mod.fd_tno_apply(params, cfg, x)
    khat = fd_mod.kernel_spectrum(params, cfg, 24)
    xhat = jnp.fft.rfft(x, n=48, axis=1)
    y_legacy = jnp.fft.irfft(xhat * khat.T[None], n=48, axis=1)[:, :24]
    assert _rel(y, y_legacy) <= 1e-6

    tcfg = TNOConfig(d=6, variant="fd", causal=True)
    tp, _ = unbox(tno_init(jax.random.PRNGKey(2), tcfg))
    plan = tno_plan(tp, tcfg, 24)
    assert "khat_real" in plan and plan["khat_real"].shape == (6, 25)
    assert _rel(tno_apply(tp, tcfg, x, plan=plan),
                tno_apply(tp, tcfg, x)) == 0.0


def test_kernel_spectrum_real_rejects_bidirectional():
    cfg = fd_mod.FDConfig(d=4, causal=False)
    params, _ = unbox(fd_mod.fd_init(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError):
        fd_mod.kernel_spectrum_real(params, cfg, 8)


def test_fd_block_grad_parity_pallas_vs_ref():
    """jax.grad through a whole causal FD GTU block: Pallas (interpret)
    path vs reference path — parameter grads flow through the RPE and
    match (the training-path acceptance gate)."""
    from repro.core.tno import TNOConfig, tno_init, tno_plan, tno_apply
    cfg_p = TNOConfig(d=8, variant="fd", causal=True, use_pallas=True)
    cfg_r = TNOConfig(d=8, variant="fd", causal=True, use_pallas=False)
    params, _ = unbox(tno_init(jax.random.PRNGKey(0), cfg_p))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))

    def loss(cfg):
        def f(p):
            plan = tno_plan(p, cfg, 16)
            return jnp.sum(jnp.sin(tno_apply(p, cfg, x, plan=plan)))
        return f

    g_p = jax.grad(loss(cfg_p))(params)
    g_r = jax.grad(loss(cfg_r))(params)
    flat_p, _ = jax.tree_util.tree_flatten(g_p)
    flat_r, _ = jax.tree_util.tree_flatten(g_r)
    for a, b in zip(flat_p, flat_r):
        assert _rel(a, b) <= 1e-5


# ------------------------------------------- signal-side DFT on the MXU
def _fd_tno_f64(x, khat, g):
    """float64 numpy evaluation of the op and its two cotangents: y, dx
    and dkhat_real for the output cotangent g (the module docstring's
    formulas, every FFT in float64)."""
    x, khat, g = (np.asarray(a, np.float64) for a in (x, khat, g))
    n = x.shape[1]
    t = np.arange(2 * n)
    w = np.where((t == 0) | (t == n), 1.0, np.where(t < n, 2.0, 0.0))
    kc = np.fft.rfft(np.fft.irfft(khat, 2 * n, axis=-1) * w, axis=-1).T
    xh = np.fft.rfft(x, 2 * n, axis=1)
    gh = np.fft.rfft(g, 2 * n, axis=1)
    y = np.fft.irfft(xh * kc, 2 * n, axis=1)[:, :n]
    dx = np.fft.irfft(gh * np.conj(kc), 2 * n, axis=1)[:, :n]
    dkt = np.fft.irfft((gh * np.conj(xh)).sum(0).T, 2 * n, axis=-1) * w
    c = np.full(n + 1, 2.0)
    c[0] = c[n] = 1.0                      # irfft's adjoint on a real input
    dk = np.fft.rfft(dkt, axis=-1).real * c / (2 * n)
    return y, dx, dk


@pytest.mark.parametrize("n,n1", [(8192, 128), (1024, 128), (512, 64),
                                  (256, 32), (128, 16), (4096, 128),
                                  (33, None), (64, None), (32, None),
                                  (1536, None), (16384, None)])
def test_mxu_split(n, n1):
    """2n = n1·n2 with n1 = 128 (fewer for short n), both multiples of 16
    and n2 ≤ 128; every other length keeps XLA's FFT."""
    assert fd_fused.mxu_split(n) == n1


MXU_SHAPES = [(2, 256, 128), (1, 1024, 128), (2, 256, 96)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,n,d", MXU_SHAPES)
def test_fd_tno_mxu_fwd_matches_f64_and_oracle(b, n, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(10), (b, n, d)).astype(dtype)
    khat = jax.random.normal(jax.random.PRNGKey(11), (d, n + 1)).astype(dtype)
    y = ops.fd_tno(x, khat, use_pallas=True, interpret=True)
    assert y.dtype == dtype
    assert fd_fused.counters["fwd_mxu"] == 1
    assert fd_fused.counters["fwd_xla"] == 0
    y64 = _fd_tno_f64(x.astype(jnp.float32), khat.astype(jnp.float32),
                      np.zeros((b, n, d)))[0]
    tol = GRAD_TOL[jnp.dtype(dtype)]
    assert _rel(y, y64) <= tol
    assert _rel(y, ref.fd_tno_ref(x, khat)) <= tol


@pytest.mark.parametrize("b,n,d", MXU_SHAPES)
def test_fd_tno_mxu_grad_matches_f64_and_oracle(b, n, d):
    """The backward MXU kernel (ĝ and x̂ once each, dx and the batch-summed
    k̂ cotangent) against float64 numpy and the oracle's autodiff."""
    ks = jax.random.split(jax.random.PRNGKey(b * n + d), 3)
    x = jax.random.normal(ks[0], (b, n, d))
    khat = jax.random.normal(ks[1], (d, n + 1))
    g = jax.random.normal(ks[2], (b, n, d))
    _, pull = jax.vjp(lambda x_, k_: ops.fd_tno(
        x_, k_, use_pallas=True, interpret=True), x, khat)
    dx, dk = pull(g)
    assert fd_fused.counters["bwd_mxu"] == 1
    assert fd_fused.counters["bwd_xla"] == 0
    _, dx64, dk64 = _fd_tno_f64(x, khat, g)
    _, pull_ref = jax.vjp(ref.fd_tno_ref, x, khat)
    dx_ref, dk_ref = pull_ref(g)
    tol = GRAD_TOL[jnp.dtype(jnp.float32)]
    assert _rel(dx, dx64) <= tol and _rel(dx, dx_ref) <= tol, "dx mismatch"
    assert _rel(dk, dk64) <= tol and _rel(dk, dk_ref) <= tol, "dkhat mismatch"


@pytest.mark.parametrize("b,n,d,path", [(3, 8192, 512, "mxu"),
                                        (2, 33, 5, "xla")])
def test_fd_tno_path_by_shape_traced(b, n, d, path):
    """Trace only: value-and-grad at train_fd_8k's shape takes the MXU
    kernels in both directions; an odd length keeps XLA's FFT in both."""
    x = jax.ShapeDtypeStruct((b, n, d), jnp.float32)
    khat = jax.ShapeDtypeStruct((d, n + 1), jnp.float32)
    jax.eval_shape(jax.value_and_grad(
        lambda x_, k_: jnp.sum(ops.fd_tno(x_, k_, use_pallas=True,
                                          interpret=True)),
        argnums=(0, 1)), x, khat)
    other = "xla" if path == "mxu" else "mxu"
    assert fd_fused.counters[f"fwd_{path}"] == 1
    assert fd_fused.counters[f"bwd_{path}"] == 1
    assert fd_fused.counters[f"fwd_{other}"] == 0
    assert fd_fused.counters[f"bwd_{other}"] == 0


def test_fd_tno_mxu_grad_ref_escape_hatch():
    """REPRO_PALLAS_GRAD=0 at a length the MXU kernels take: the forward
    stays on ``fd_mxu_fwd``, the backward is the jnp reference formulas."""
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 256, 128))
    khat = jax.random.normal(jax.random.PRNGKey(13), (128, 257))
    backend.set_default_pallas_grad(False)
    try:
        g = jax.grad(lambda x_: jnp.sum(jnp.sin(
            ops.fd_tno(x_, khat, use_pallas=True, interpret=True))))(x)
    finally:
        backend.set_default_pallas_grad(None)
    g_want = jax.grad(lambda x_: jnp.sum(jnp.sin(ref.fd_tno_ref(x_, khat))))(x)
    assert _rel(g, g_want) <= 1e-5
    assert fd_fused.counters["fwd_mxu"] == 1
    assert fd_fused.counters["bwd_ref"] == 1
    assert fd_fused.counters["bwd_mxu"] == 0
