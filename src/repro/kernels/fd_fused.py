"""Fused causal FD-TNO Pallas pipeline (paper §3.3, Algorithm 2).

The causal frequency-domain TNO models only the *real* part ``khat`` of the
kernel's frequency response on the rfft grid and recovers the imaginary
part with a discrete Hilbert transform (``khat - i·H{khat}``, realised as
the analytic-signal window in the lag variable — the numerically-exact
form pinned by tests/test_hilbert.py). The jnp path in core/fd.py runs
this as five separate XLA ops with the (b, n+1, d) complex spectrum
crossing HBM between each. This module is the FD sibling of the fused SKI
stack (kernels/ski_fused.py + ski_vjp.py).

The signal side — rfft(x), the multiply by k̂, irfft, and in the backward
rfft(g), rfft(x), irfft(ĝ ⊙ conj k̂) and Σ_b ĝ ⊙ conj(x̂) — runs, where 2n
factors (:func:`mxu_split`), as one Pallas kernel per direction: a DFT in
matmul form on the MXU whose stages stay in VMEM, so HBM sees x, k̂ and y
only (``fd_mxu_fwd``, ``fd_mxu_bwd``; see the section's comment). XLA's
TPU FFT is itself matmul-form, but writes every stage, the padded half
of zeros and a relayout of the (b, 2n, d) tensor to HBM. Other lengths
keep XLA's (i)rfft on the signal, and the kernel side (the (d, 2n)
response) keeps XLA's FFTs on every path, with blocked Pallas kernels
between XLA's stages:

* ``hilbert_window_pallas`` — the analytic-signal lag window (1, 2, …, 2,
  1, 0, …, 0) applied to the kernel's time response, blocked over
  (d-tile, lag-tile), the window regenerated in-kernel from iota. The
  window is diagonal ⇒ self-adjoint: its custom VJP is the same kernel.
* ``fd_spectral_multiply_pallas`` — the per-channel complex spectral
  multiply ŷ = x̂ ⊙ k̂ on re/im planes (Pallas TPU has no complex dtype),
  blocked over (batch, freq-tile, d-tile): both output planes produced by
  one kernel / one read of x̂ — the (b, n+1, d) round-trips between
  ``real·real``/``imag·imag`` element-wise ops collapse into one pass.
* ``fd_khat_grad_pallas`` — the backward's per-tile reduction kernel:
  Σ_b ĝ ⊙ conj(x̂) accumulated over the innermost batch grid axis
  (consecutive output revisits — the safe Pallas accumulation pattern,
  same as ski_grad).

The differentiable op is :func:`fd_tno_pallas` (dispatched by
``ops.fd_tno``): a ``jax.custom_vjp`` whose backward *is the forward
pipeline with the spectrum conjugated* — the adjoint of a causal
circular convolution is the anticausal correlation, i.e. the identical
pipeline with k̂ → conj(k̂) — plus the reduction Σ_b ĝ ⊙ conj(x̂) for
the k̂ cotangent (in ``fd_mxu_bwd``, or the two XLA-path kernels above).
All cotangents are exact linear-operator adjoints (circular correlation
theorem), not FFT-adjoint approximations:

    y   = slice_n( irfft( rfft(pad x) ⊙ k̂ ) )       k̂ = rfft(w ⊙ irfft(khat))
    dx  = slice_n( irfft( rfft(pad g) ⊙ conj k̂ ) )   forward kernel, conj spectrum
    dk̂_time = irfft( Σ_b rfft(pad g) ⊙ conj(rfft(pad x)) )   reduction kernel
    dkhat   = irfftᵀ( w ⊙ dk̂_time )                 window kernel again (wᵀ = w)

Residual policy matches the SKI ops: inputs only (x, khat_real); the
spectra are recomputed in the backward. ``REPRO_PALLAS_GRAD=0`` swaps the
backward to the jnp reference cotangents (counters record which path ran —
no silent fallback, the ski_vjp contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

# trace-time instrumentation, same contract as kernels/ski_vjp.py: tests
# (and the trainer banner) assert training never silently falls back to
# the jnp reference path
counters = {"fwd": 0, "bwd_kernel": 0, "bwd_ref": 0,
            # which signal path each traced call took (mxu_split)
            "fwd_mxu": 0, "fwd_xla": 0, "bwd_mxu": 0, "bwd_xla": 0}


def reset_counters() -> None:
    for k in counters:
        counters[k] = 0


# ------------------------------------------------------ hilbert lag window
def _window_kernel(k_ref, o_ref, *, n, bt):
    ti = pl.program_id(1)
    t = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 1) + ti * bt
    w = jnp.where((t == 0) | (t == n), 1.0,
                  jnp.where(t < n, 2.0, 0.0))
    o_ref[...] = (k_ref[...].astype(jnp.float32) * w).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "interpret", "bd", "bt"))
def _window_call(kt, n: int, *, interpret, bd, bt):
    d, tt = kt.shape
    grid = (d // bd, tt // bt)
    return pl.pallas_call(
        functools.partial(_window_kernel, n=n, bt=bt),
        grid=grid,
        in_specs=[pl.BlockSpec((bd, bt), lambda di, ti: (di, ti))],
        out_specs=pl.BlockSpec((bd, bt), lambda di, ti: (di, ti)),
        out_shape=jax.ShapeDtypeStruct((d, tt), kt.dtype),
        interpret=interpret,
    )(kt)


def _window_padded(kt, n, interpret, bd, bt):
    d, tt = kt.shape
    dp, tp = backend.round_up(d, bd), backend.round_up(tt, bt)
    if dp != d or tp != tt:
        out = _window_call(jnp.pad(kt, ((0, dp - d), (0, tp - tt))), n,
                           interpret=interpret, bd=bd, bt=bt)
        return out[:d, :tt]
    return _window_call(kt, n, interpret=interpret, bd=bd, bt=bt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _window_core(kt, n, interpret, bd, bt):
    return _window_padded(kt, n, interpret, bd, bt)


def _window_core_fwd(kt, n, interpret, bd, bt):
    return _window_core(kt, n, interpret, bd, bt), None


def _window_core_bwd(n, interpret, bd, bt, res, g):
    del res                                   # diagonal window: residual-free
    if not backend.resolve_pallas_grad():
        from repro.kernels import ref
        return (ref.hilbert_window_ref(g, n),)
    return (_window_padded(g, n, interpret, bd, bt),)


_window_core.defvjp(_window_core_fwd, _window_core_bwd)


def hilbert_window_pallas(kt, n: int, *, interpret=None, bd=None, bt=None):
    """Analytic-signal lag window of :func:`repro.core.hilbert.causal_spectrum`:
    keep lag 0 and lag n, double lags 1..n-1, zero lags n+1..  (causal ⇒
    the irfft of the windowed response vanishes on negative lags exactly).

    kt: (d, 2n) time response (``irfft(khat_real)``). The window is
    diagonal, hence self-adjoint — differentiable via a custom VJP that is
    this same kernel. Matches ref.hilbert_window_ref.
    """
    d, tt = kt.shape
    interpret = backend.resolve_interpret(interpret)
    if bd is None or bt is None:
        tune = None
        if backend.is_concrete(kt):
            tune = lambda BD, BT: _window_padded(kt, n, interpret, BD, BT)
        # get_blocks keys on (sublane-dim, lane-dim): here (d, lag)
        hbd, hbt = backend.get_blocks("hilbert_window", d, tt, kt.dtype,
                                      interpret,
                                      tune_call=tune, extra=f"n={n}")
        bd = bd or hbd
        bt = bt or hbt
    bd, bt = backend.clamp_blocks(bd, bt, d, tt, interpret)
    return _window_core(kt, int(n), interpret, bd, bt)


# ------------------------------------------------- complex spectral multiply
def _mul_kernel(xr_ref, xi_ref, kr_ref, ki_ref, yr_ref, yi_ref):
    xr = xr_ref[0].astype(jnp.float32)
    xi = xi_ref[0].astype(jnp.float32)
    kr = kr_ref[...].astype(jnp.float32)
    ki = ki_ref[...].astype(jnp.float32)
    yr_ref[0] = (xr * kr - xi * ki).astype(yr_ref.dtype)
    yi_ref[0] = (xr * ki + xi * kr).astype(yi_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "bf", "bd"))
def _mul_call(xr, xi, kr, ki, *, interpret, bf, bd):
    b, f, d = xr.shape
    grid = (b, d // bd, f // bf)
    xspec = pl.BlockSpec((1, bf, bd), lambda bi, di, fi: (bi, fi, di))
    kspec = pl.BlockSpec((bf, bd), lambda bi, di, fi: (fi, di))
    out = jax.ShapeDtypeStruct((b, f, d), jnp.float32)
    return pl.pallas_call(
        _mul_kernel,
        grid=grid,
        in_specs=[xspec, xspec, kspec, kspec],
        out_specs=[pl.BlockSpec((1, bf, bd), lambda bi, di, fi: (bi, fi, di))] * 2,
        out_shape=[out, out],
        interpret=interpret,
    )(xr, xi, kr, ki)


def _mul_padded(xr, xi, kr, ki, interpret, bf, bd):
    b, f, d = xr.shape
    fp, dp = backend.round_up(f, bf), backend.round_up(d, bd)
    if fp != f or dp != d:
        padx = ((0, 0), (0, fp - f), (0, dp - d))
        padk = ((0, fp - f), (0, dp - d))
        yr, yi = _mul_call(jnp.pad(xr, padx), jnp.pad(xi, padx),
                           jnp.pad(kr, padk), jnp.pad(ki, padk),
                           interpret=interpret, bf=bf, bd=bd)
        return yr[:, :f, :d], yi[:, :f, :d]
    return _mul_call(xr, xi, kr, ki, interpret=interpret, bf=bf, bd=bd)


def fd_spectral_multiply_pallas(xr, xi, kr, ki, *, interpret=None, bf=None,
                                bd=None):
    """Per-channel complex spectral multiply on re/im planes, one kernel.

    xr, xi: (b, F, d) signal-spectrum planes (F = n+1 rfft bins);
    kr, ki: (F, d) kernel-spectrum planes. Returns (yr, yi), fp32.
    Matches ref.fd_spectral_multiply_ref. The backward sibling is this
    same kernel with the kernel spectrum conjugated (ki → -ki) — see
    :func:`fd_tno_pallas`.
    """
    b, f, d = xr.shape
    interpret = backend.resolve_interpret(interpret)
    if bf is None or bd is None:
        tune = None
        if backend.is_concrete(xr, xi, kr, ki):
            tune = lambda BF, BD: _mul_padded(xr, xi, kr, ki, interpret,
                                              BF, BD)
        hbf, hbd = backend.get_blocks("fd_mul", f, d, xr.dtype, interpret,
                                      tune_call=tune)
        bf = bf or hbf
        bd = bd or hbd
    bf, bd = backend.clamp_blocks(bf, bd, f, d, interpret)
    return _mul_padded(xr, xi, kr, ki, interpret, bf, bd)


# --------------------------------------------------- khat cotangent reduce
def _khat_grad_kernel(gr_ref, gi_ref, xr_ref, xi_ref, dr_ref, di_ref):
    bi = pl.program_id(2)
    gr = gr_ref[0].astype(jnp.float32)
    gi = gi_ref[0].astype(jnp.float32)
    xr = xr_ref[0].astype(jnp.float32)
    xi = xi_ref[0].astype(jnp.float32)
    pr = gr * xr + gi * xi                    # Re(ĝ conj(x̂))
    pi = gi * xr - gr * xi                    # Im(ĝ conj(x̂))

    @pl.when(bi == 0)
    def _init():
        dr_ref[...] = pr
        di_ref[...] = pi

    @pl.when(bi > 0)
    def _acc():
        dr_ref[...] = dr_ref[...] + pr
        di_ref[...] = di_ref[...] + pi


@functools.partial(jax.jit, static_argnames=("interpret", "bf", "bd"))
def _khat_grad_call(gr, gi, xr, xi, *, interpret, bf, bd):
    b, f, d = xr.shape
    grid = (d // bd, f // bf, b)              # batch innermost: consecutive
    xspec = pl.BlockSpec((1, bf, bd), lambda di, fi, bi: (bi, fi, di))
    ospec = pl.BlockSpec((bf, bd), lambda di, fi, bi: (fi, di))
    out = jax.ShapeDtypeStruct((f, d), jnp.float32)
    return pl.pallas_call(
        _khat_grad_kernel,
        grid=grid,
        in_specs=[xspec] * 4,
        out_specs=[ospec] * 2,
        out_shape=[out, out],
        interpret=interpret,
    )(gr, gi, xr, xi)


def fd_khat_grad_pallas(gr, gi, xr, xi, *, interpret=None, bf=None, bd=None):
    """Per-tile batch-reduction of the kernel-spectrum cotangent:
    (dkr, dki) = planes of Σ_b ĝ ⊙ conj(x̂) → (F, d) fp32 each.

    The irfft of this is *exactly* the time-domain cotangent of the causal
    kernel (circular correlation theorem) — no FFT-adjoint scaling enters.
    Matches ref.fd_khat_grad_ref.
    """
    b, f, d = xr.shape
    interpret = backend.resolve_interpret(interpret)
    if bf is None or bd is None:
        bf, bd = backend.get_blocks("fd_khat_grad", f, d, xr.dtype, interpret)
    bf, bd = backend.clamp_blocks(bf, bd, f, d, interpret)
    fp, dp = backend.round_up(f, bf), backend.round_up(d, bd)
    if fp != f or dp != d:
        pad = ((0, 0), (0, fp - f), (0, dp - d))
        dr, di = _khat_grad_call(jnp.pad(gr, pad), jnp.pad(gi, pad),
                                 jnp.pad(xr, pad), jnp.pad(xi, pad),
                                 interpret=interpret, bf=bf, bd=bd)
        return dr[:f, :d], di[:f, :d]
    return _khat_grad_call(gr, gi, xr, xi, interpret=interpret, bf=bf, bd=bd)


# ---------------------------------------------- signal-side DFT on the MXU
# The signal transforms of the op run as a DFT in matmul form inside one
# kernel per direction, with every stage in VMEM. For 2n = n1·n2, time is
# t = n2·t1 + t2 and frequency k = k1 + n1·k2 (Cooley–Tukey):
#
#   X[k1+n1·k2] = Σ_t2 ω_n2^(k2·t2) ω_2n^(k1·t2) Σ_t1 ω_n1^(k1·t1) x[n2·t1+t2]
#
# Stage 1 is one (2·n1 × n1/2) @ (n1/2 × 128) matmul per t2 — the padded
# half of x is t1 ≥ n1/2 and is never read, and the twiddle ω_2n^(k1·t2)
# is folded into that t2's matrix. Stage 2 is one (n2 × 2·n2) @ (2·n2 ×
# 128) matmul per k1 and keeps only the k2 < n2/2 bins, i.e. k < n; the
# Nyquist bin k = n is the ±1-weighted sum of x. The inverse runs the same
# stages backwards and produces only the lags t < n. Channels stay on the
# lanes. x and y are read and written as (n1/2, n2, lanes) blocks and the
# stage planes live as (n2, 2·n1, lanes), so a stage that contracts the
# other index takes 8 of them at once as a (·, 8, lanes) window and swaps
# its two leading axes in registers — a sublane gather one row at a time
# costs four times as many bundles. Every matmul is float32-equivalent,
# the six bf16 products of Mosaic's HIGHEST: the constant side is split
# into bf16 parts on the host, and the data's parts are stacked along K so
# that one bf16 matmul sums all six (:func:`_rhs6`, :func:`_rfft_stage1`);
# the inverse's last stage, whose matrix is stage 1's transposed, runs at
# HIGHEST. Spectra stay in the transform's (k1, k2) order: only the
# (n+1, d) kernel spectrum and the backward's k̂ cotangent are permuted,
# in XLA, once a call.
_LANES = 128
_G = 8                           # the rows of one (8, 128) float32 tile
_VMEM_LIMIT = 100 * 2 ** 20      # a v5e TensorCore has 128 MiB of VMEM
_HIGHEST = jax.lax.Precision.HIGHEST


def mxu_split(n: int) -> int | None:
    """n1 of the split 2n = n1·n2 the MXU kernels use at length n, or None
    where the op keeps XLA's FFT. n1 = 128 (fewer for short n); n1 and n2
    multiples of 16, so both half-planes are whole (8, 128) tiles; n2 ≤
    128: at n2 = 256 the stage planes, matrices and blocks pass the
    kernels' VMEM limit (the backward needs 144 MiB of a v5e core's 128)."""
    big_n = 2 * n
    n1 = min(128, big_n // 16)
    if n1 < 16 or n1 % 16 or big_n % n1:
        return None
    n2 = big_n // n1
    return n1 if n2 % 16 == 0 and n2 <= 128 else None


@functools.lru_cache(maxsize=None)
def _dft_tables_host(n: int, n1: int):
    """Host float64 → float32 DFT matrices for the split 2n = n1·n2 (host
    numpy, like core/fd._omega_grid_host, so no device buffer is cached):

    fw (n2, 2·n1, n1/2): per t2, [Re; Im] of ω_n1^(k1·t1)·ω_2n^(k1·t2);
        its transpose (scaled by 2/2n in ``h``) is the inverse's last stage.
    f2 (n2, 2, 2·n1, n1): fw's bfloat16 parts side by side, [hi | mid] and
        [hi | lo]: with K only n1/2 deep, two of stage 1's six products
        share each 128-deep pass of the MXU (:func:`_rfft_stage1`).
    g (n2, 2·n2): [[Re, -Im], [Im, Re]] of ω_n2^(k2·t2), k2 < n2/2.
    h (2·n2, n2): the same for ω_n2^(-k2·t2) × 2/2n (irfft's scale, the
        doubled Hermitian half).
    g and h are returned split by :func:`_split6`, as bfloat16.
    """
    big_n = 2 * n
    n2 = big_n // n1
    k1 = np.arange(n1)[None, :, None]
    t1 = np.arange(n1 // 2)[None, None, :]
    t2 = np.arange(n2)[:, None, None]
    k2 = np.arange(n2 // 2)

    def w(a, m):
        return np.exp(-2j * np.pi * (a % m) / m)

    e = w(k1 * t2, big_n) * w(k1 * t1, n1)                  # (n2, n1, n1/2)
    fw = np.concatenate([e.real, e.imag], axis=1)
    gk = w(k2[:, None] * np.arange(n2)[None, :], n2)        # (n2/2, n2)
    g = np.block([[gk.real, -gk.imag], [gk.imag, gk.real]])
    hk = np.conj(gk).T * (2.0 / big_n)                      # (n2, n2/2)
    h = np.block([[hk.real, -hk.imag], [hk.imag, hk.real]])
    hi, mid, lo = _bf16_parts(fw.astype(np.float32))
    f2 = np.stack([np.concatenate([hi, mid], axis=2),
                   np.concatenate([hi, lo], axis=2)], axis=1)
    return fw.astype(np.float32), f2, _split6(g), _split6(h)


def _bf16_parts(a):
    """float32 → three bfloat16 parts whose float32 sum is ``a`` to within
    float32 rounding: hi, mid, lo."""
    hi = a.astype(jnp.bfloat16)
    r = a - hi.astype(np.float32)
    mid = r.astype(jnp.bfloat16)
    return hi, mid, (r - mid.astype(np.float32)).astype(jnp.bfloat16)


def _split6(a):
    """(M, K) float64 matrix → (M, 6K) bfloat16 [hi, hi, mid, hi, mid, lo],
    the left-hand side of :func:`_rhs6`'s six products."""
    hi, mid, lo = _bf16_parts(np.asarray(a, np.float32))   # numpy, on host
    return np.concatenate([hi, hi, mid, hi, mid, lo], axis=1)


def _gather(ref, start):
    """(a, rows, lanes) ref → (8, a, lanes) value: rows start … start+7 of
    every leading index, leading axes swapped."""
    return jnp.swapaxes(ref[:, pl.ds(pl.multiple_of(start, _G), _G), :], 0, 1)


def _scatter(ref, start, rows):
    """Inverse of :func:`_gather`: 8 (a, lanes) values → rows start … start+7
    of every leading index of an (a, rows, lanes) ref."""
    ref[:, pl.ds(pl.multiple_of(start, _G), _G), :] = jnp.swapaxes(
        jnp.stack(rows), 0, 1).astype(ref.dtype)


def _groups(count, body, init=0):
    return jax.lax.fori_loop(0, count // _G,
                             lambda i, c: body(i * _G, c), init)


def _rfft_stage1(x_ref, f2_ref, z_ref, *, n2):
    """z[t2] = fw[t2] @ x[:, t2] for every t2 (x_ref (n1/2, n2, lanes),
    z_ref (n2, 2·n1, lanes)); returns the Nyquist bin Σ_t (-1)^t x[t]
    (row k1 = 0 of stage 1 is Σ_t1 x, and t ≡ t2 mod 2). One bfloat16
    matmul a t2 of fw's six products: [hi|mid, hi|mid, hi|lo] against x's
    parts [hi; hi; mid; mid; lo; hi]."""
    def body(t0, nyq):
        hi, mid, lo = _bf16_parts(_gather(x_ref, t0).astype(jnp.float32))
        xs = jnp.concatenate([hi, hi, mid, mid, lo, hi], axis=1)
        for j in range(_G):
            hm, hl = f2_ref[t0 + j, 0], f2_ref[t0 + j, 1]
            a = jnp.dot(jnp.concatenate([hm, hm, hl], axis=1), xs[j],
                        preferred_element_type=jnp.float32)
            z_ref[t0 + j] = a
            nyq = nyq + (1.0 - 2.0 * (j % 2)) * a[0:1]
        return nyq

    return _groups(n2, body, jnp.zeros((1, _LANES), jnp.float32))


def _irfft_stage2(z_ref, fw_ref, o_ref, nyq, *, n2):
    """o[:, t2] = fw[t2]ᵀ @ z[t2] + (-1)^t2·nyq: the lags t < n of the
    inverse (o_ref (n1/2, n2, lanes)); ``nyq`` is Re Y[n] / 2n."""
    def body(t0, c):
        rows = []
        for j in range(_G):
            yb = jax.lax.dot_general(
                fw_ref[t0 + j], z_ref[t0 + j], (((0,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)
            rows.append(yb + (1.0 - 2.0 * (j % 2)) * nyq)
        _scatter(o_ref, t0, rows)
        return c

    _groups(n2, body)


def _rhs6(b):
    """(…, K, lanes) float32 → (…, 6K, lanes) bfloat16 [hi, mid, hi, lo, mid,
    hi], the right-hand side of a :func:`_split6` matrix's six products.
    Split for a whole group of 8 at once: 8% fewer bundles in the middle
    stage than a split per matmul (static count of the compiled kernel)."""
    hi, mid, lo = _bf16_parts(b.astype(jnp.float32))
    return jnp.concatenate([hi, mid, hi, lo, mid, hi], axis=-2)


def _spectra(z_ref, g_ref, k0, *, n1):
    """Stage 2 for k1 = k0 … k0+7: (8, n2, lanes), each [re; im] of the
    bins k1 + n1·k2, k2 < n2/2."""
    q = _rhs6(jnp.concatenate([_gather(z_ref, k0), _gather(z_ref, n1 + k0)],
                              axis=1))
    return jnp.stack([jnp.dot(g_ref[...], q[j],
                              preferred_element_type=jnp.float32)
                      for j in range(_G)])


def _inverse_columns(z_ref, h_ref, k0, yr, yi, *, n1, n2):
    """The inverse stage over k2 for k1 = k0 … k0+7 ((8, n2/2, lanes) re
    and im), written back to the columns :func:`_spectra` read."""
    y = _rhs6(jnp.concatenate([yr, yi], axis=1))
    b = [jnp.dot(h_ref[...], y[j], preferred_element_type=jnp.float32)
         for j in range(_G)]
    _scatter(z_ref, k0, [v[:n2] for v in b])
    _scatter(z_ref, n1 + k0, [v[n2:] for v in b])


def _split_rows(k0, k2):
    """Rows k1·(n2/2) … of the split-order spectrum for k1 = k0 … k0+7."""
    return pl.ds(pl.multiple_of(k0 * k2, _G * k2), _G * k2)


def _fwd_mxu_kernel(x_ref, kr_ref, ki_ref, kn_ref, fw_ref, f2_ref, g_ref,
                    h_ref, y_ref, z_ref, *, n1, n2):
    nyq = _rfft_stage1(x_ref, f2_ref, z_ref, n2=n2)
    k2 = n2 // 2

    def body(k0, c):
        xs = _spectra(z_ref, g_ref, k0, n1=n1)
        xr, xi = xs[:, :k2], xs[:, k2:]
        rows = _split_rows(k0, k2)
        kr = kr_ref[rows, :].reshape(_G, k2, _LANES)
        ki = ki_ref[rows, :].reshape(_G, k2, _LANES)
        _inverse_columns(z_ref, h_ref, k0, xr * kr - xi * ki,
                         xr * ki + xi * kr, n1=n1, n2=n2)
        return c

    _groups(n1, body)
    _irfft_stage2(z_ref, fw_ref, y_ref, nyq * kn_ref[...] / (n1 * n2), n2=n2)


def _bwd_mxu_kernel(g_ref, x_ref, kr_ref, ki_ref, kn_ref, fw_ref, f2_ref,
                    gm_ref, h_ref, dx_ref, dkr_ref, dki_ref, dkn_ref, z_ref,
                    s_ref, *, n1, n2):
    """One row of the backward: x̂ = rfft(x) into ``s``, then ĝ = rfft(g);
    dx = irfft(ĝ ⊙ conj k̂) and Σ_b ĝ ⊙ conj(x̂) accumulated into the k̂
    cotangent over the innermost (row) grid axis."""
    k2 = n2 // 2

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dkr_ref[...] = jnp.zeros_like(dkr_ref)
        dki_ref[...] = jnp.zeros_like(dki_ref)
        dkn_ref[...] = jnp.zeros_like(dkn_ref)

    def s_rows(k0):
        return pl.ds(pl.multiple_of(k0 * n2, _G * n2), _G * n2)

    def put_x(k0, c):
        s_ref[s_rows(k0), :] = _spectra(z_ref, gm_ref, k0, n1=n1).reshape(
            _G * n2, _LANES)
        return c

    nyq_x = _rfft_stage1(x_ref, f2_ref, z_ref, n2=n2)
    _groups(n1, put_x)
    nyq_g = _rfft_stage1(g_ref, f2_ref, z_ref, n2=n2)

    def body(k0, c):
        gs = _spectra(z_ref, gm_ref, k0, n1=n1)
        gr, gi = gs[:, :k2], gs[:, k2:]
        xs = s_ref[s_rows(k0), :].reshape(_G, n2, _LANES)
        xr, xi = xs[:, :k2], xs[:, k2:]
        rows = _split_rows(k0, k2)
        dkr_ref[rows, :] += (gr * xr + gi * xi).reshape(_G * k2, _LANES)
        dki_ref[rows, :] += (gi * xr - gr * xi).reshape(_G * k2, _LANES)
        kr = kr_ref[rows, :].reshape(_G, k2, _LANES)
        ki = ki_ref[rows, :].reshape(_G, k2, _LANES)
        _inverse_columns(z_ref, h_ref, k0, gr * kr + gi * ki,
                         gi * kr - gr * ki, n1=n1, n2=n2)
        return c

    _groups(n1, body)
    dkn_ref[...] += nyq_g * nyq_x
    _irfft_stage2(z_ref, fw_ref, dx_ref, nyq_g * kn_ref[...] / (n1 * n2),
                  n2=n2)


def _mxu_specs(n, n1):
    row = pl.BlockSpec((None, n1 // 2, 2 * n // n1, _LANES),
                       lambda c, r: (r, 0, 0, c))
    # the spectrum's block changes only with the channel tile: one buffer
    spec = pl.BlockSpec((n, _LANES), lambda c, r: (0, c),
                        pipeline_mode=pl.Buffered(1))
    nyq = pl.BlockSpec((1, _LANES), lambda c, r: (0, c))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return row, spec, nyq, whole


@functools.partial(jax.jit, static_argnames=("n1", "interpret"))
def _fwd_mxu_call(x, kpr, kpi, kn, *, n1, interpret):
    b, n, d = x.shape
    n2 = 2 * n // n1
    row, spec, nyq, whole = _mxu_specs(n, n1)
    rows = (b, n1 // 2, n2, d)
    y = pl.pallas_call(
        functools.partial(_fwd_mxu_kernel, n1=n1, n2=n2),
        name="fd_mxu_fwd",
        grid=(d // _LANES, b),
        in_specs=[row, spec, spec, nyq] + [whole] * 4,
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct(rows, jnp.float32),
        scratch_shapes=[pltpu.VMEM((n2, 2 * n1, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x.reshape(rows), kpr, kpi, kn,
      *map(jnp.asarray, _dft_tables_host(n, n1)))
    return y.reshape(b, n, d)


@functools.partial(jax.jit, static_argnames=("n1", "interpret"))
def _bwd_mxu_call(g, x, kpr, kpi, kn, *, n1, interpret):
    b, n, d = x.shape
    n2 = 2 * n // n1
    row, spec, nyq, whole = _mxu_specs(n, n1)
    rows = (b, n1 // 2, n2, d)
    plane = jax.ShapeDtypeStruct((n, d), jnp.float32)
    dx, dkr, dki, dkn = pl.pallas_call(
        functools.partial(_bwd_mxu_kernel, n1=n1, n2=n2),
        name="fd_mxu_bwd",
        grid=(d // _LANES, b),               # rows innermost: Σ_b in place
        in_specs=[row, row, spec, spec, nyq] + [whole] * 4,
        out_specs=[row, spec, spec, nyq],
        out_shape=[jax.ShapeDtypeStruct(rows, jnp.float32), plane,
                   plane, jax.ShapeDtypeStruct((1, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n2, 2 * n1, _LANES), jnp.float32),
                        pltpu.VMEM((n1 * n2, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(g.reshape(rows), x.reshape(rows), kpr, kpi, kn,
      *map(jnp.asarray, _dft_tables_host(n, n1)))
    return dx.reshape(b, n, d), dkr, dki, dkn


def _to_split_order(p, n1):
    """(n, d) plane in bin order k = k1 + n1·k2 → rows k1·(n/n1) + k2."""
    n, d = p.shape
    return p.reshape(n // n1, n1, d).transpose(1, 0, 2).reshape(n, d)


def _from_split_order(p, n1):
    n, d = p.shape
    return p.reshape(n1, n // n1, d).transpose(1, 0, 2).reshape(n, d)


def _pad_lanes(a):
    """Channels (last axis) padded to whole lane tiles, as float32."""
    d = a.shape[-1]
    pad = [(0, 0)] * (a.ndim - 1) + [(0, backend.round_up(d, _LANES) - d)]
    return jnp.pad(a.astype(jnp.float32), pad)


def _split_spectrum(kr, ki, n1):
    """The (n+1, d) planes of k̂ → the kernels' operands: both planes in
    split order with bin 0 halved (the inverse doubles every bin < n), and
    Re k̂[n] on its own, channels padded to whole lane tiles."""
    n = kr.shape[0] - 1
    half = jnp.ones((n, 1), jnp.float32).at[0].set(0.5)
    return (_pad_lanes(_to_split_order(kr[:n], n1) * half),
            _pad_lanes(_to_split_order(ki[:n], n1) * half),
            _pad_lanes(kr[n:]))


def fd_conv_mxu(x, kr, ki, n1, *, interpret):
    """y = irfft(rfft(x, 2n) ⊙ k̂)[:n] by the forward MXU kernel; (kr, ki)
    are the (n+1, d) planes of k̂. Returns float32 (b, n, d)."""
    y = _fwd_mxu_call(_pad_lanes(x), *_split_spectrum(kr, ki, n1), n1=n1,
                      interpret=interpret)
    return y[..., :x.shape[-1]]


def fd_conv_grad_mxu(g, x, kr, ki, n1, *, interpret):
    """The backward MXU kernel: (dx, dkr, dki) with dx = irfft(ĝ ⊙ conj
    k̂)[:n] and (dkr, dki) the (n+1, d) planes of Σ_b ĝ ⊙ conj(x̂)."""
    d = x.shape[-1]
    dx, dkr, dki, dkn = _bwd_mxu_call(
        _pad_lanes(g), _pad_lanes(x), *_split_spectrum(kr, ki, n1), n1=n1,
        interpret=interpret)
    dkr = jnp.concatenate([_from_split_order(dkr, n1), dkn], axis=0)
    dki = jnp.concatenate([_from_split_order(dki, n1), jnp.zeros_like(dkn)],
                          axis=0)
    return dx[..., :d], dkr[:, :d], dki[:, :d]


# --------------------------------------------------------- the fused op
def causal_khat_planes(khat_real, interpret=None):
    """(d, n+1) real response → ((n+1), d) re/im planes of the causal
    spectrum ``khat - i·H{khat}``, the Hilbert step realised as the
    analytic lag window (Pallas) between the two staging FFTs.

    Differentiable: the window kernel carries its own custom VJP and the
    FFT stages use XLA's exact adjoints, so ``jax.vjp`` through this is
    exact (used by the op backward for the parameter-side pullback).
    """
    n = khat_real.shape[-1] - 1
    kt = jnp.fft.irfft(khat_real.astype(jnp.float32), n=2 * n, axis=-1)
    kc = hilbert_window_pallas(kt, n, interpret=interpret)
    khat = jnp.fft.rfft(kc, n=2 * n, axis=-1)                # (d, n+1)
    return jnp.real(khat).T, jnp.imag(khat).T                # (n+1, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fd_tno_pallas(x, khat_real, interpret: bool):
    """Causal FD-TNO as one differentiable op: y = irfft(rfft(x) ⊙ k̂)[:n]
    with k̂ the Hilbert-completed causal spectrum of ``khat_real``.

    x: (b, n, d); khat_real: (d, n+1) real response on the rfft grid
    (the raw RPE output — no decay bias, paper §3.3). Matches
    ref.fd_tno_ref. ``interpret`` must be resolved by the caller (static
    nondiff argument).
    """
    b, n, d = x.shape
    kr, ki = causal_khat_planes(khat_real, interpret)
    n1 = mxu_split(n)
    if n1 is not None:
        counters["fwd_mxu"] += 1
        return fd_conv_mxu(x, kr, ki, n1, interpret=interpret).astype(x.dtype)
    counters["fwd_xla"] += 1
    xhat = jnp.fft.rfft(x.astype(jnp.float32), n=2 * n, axis=1)  # (b,n+1,d)
    yr, yi = fd_spectral_multiply_pallas(jnp.real(xhat), jnp.imag(xhat),
                                         kr, ki, interpret=interpret)
    y = jnp.fft.irfft(yr + 1j * yi, n=2 * n, axis=1)[:, :n]
    return y.astype(x.dtype)


def _fd_fwd(x, khat_real, interpret):
    counters["fwd"] += 1
    return fd_tno_pallas(x, khat_real, interpret), (x, khat_real)


def _fd_bwd_ref_formulas(x, khat_real, g):
    from repro.kernels import ref
    _, vjp = jax.vjp(ref.fd_tno_ref, x, khat_real)
    return vjp(g)


def _fd_bwd(interpret, res, g):
    x, khat_real = res
    if not backend.resolve_pallas_grad():
        counters["bwd_ref"] += 1
        return _fd_bwd_ref_formulas(x, khat_real, g)
    counters["bwd_kernel"] += 1
    b, n, d = x.shape
    # recompute both spectra from the saved inputs (residuals = inputs only)
    kr, ki = causal_khat_planes(khat_real, interpret)
    n1 = mxu_split(n)
    if n1 is not None:
        counters["bwd_mxu"] += 1
        dx, dkr, dki = fd_conv_grad_mxu(g, x, kr, ki, n1,
                                        interpret=interpret)
    else:
        counters["bwd_xla"] += 1
        xhat = jnp.fft.rfft(x.astype(jnp.float32), n=2 * n, axis=1)
        ghat = jnp.fft.rfft(g.astype(jnp.float32), n=2 * n, axis=1)
        gr, gi = jnp.real(ghat), jnp.imag(ghat)
        # signal cotangent: the forward multiply kernel with the spectrum
        # conjugated — adjoint of causal conv = anticausal correlation
        dxr, dxi = fd_spectral_multiply_pallas(gr, gi, kr, -ki,
                                               interpret=interpret)
        dx = jnp.fft.irfft(dxr + 1j * dxi, n=2 * n, axis=1)[:, :n]
        dkr, dki = fd_khat_grad_pallas(gr, gi, jnp.real(xhat),
                                       jnp.imag(xhat), interpret=interpret)
    # kernel cotangent Σ_b ĝ ⊙ conj(x̂): its irfft is exactly the time
    # cotangent of the causal kernel, then the (self-adjoint) lag window
    # and the exact irfft adjoint pull it back to khat_real
    dkc = jnp.fft.irfft((dkr + 1j * dki).T, n=2 * n, axis=-1)    # (d, 2n)
    dkt = hilbert_window_pallas(dkc, n, interpret=interpret)
    _, irfft_vjp = jax.vjp(
        lambda k: jnp.fft.irfft(k.astype(jnp.float32), n=2 * n, axis=-1),
        khat_real)
    (dkhat_real,) = irfft_vjp(dkt)
    return dx.astype(x.dtype), dkhat_real.astype(khat_real.dtype)


fd_tno_pallas.defvjp(_fd_fwd, _fd_bwd)
