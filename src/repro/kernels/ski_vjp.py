"""Trainable fused SKI-TNO: custom VJP with Pallas backward kernels (PR 2).

The kernels define their own backward: a custom VJP whose cotangents are
kernel launches, so ``jax.grad`` through the fused two-pass pipeline stays
on the kernel path. Every factor of the pipeline is *linear in the signal*,
so the backward is the transposed pipeline and reuses the forward
machinery (Qin et al. 2023's TNN training at kernel speed):

Forward (kernels/interp_matvec.py pass 1 + kernels/ski_fused.py pass 2)::

    z = Wᵀ x                       (b, r, d)
    y = W (A z) + T_sparse x       (b, n, d), single output write

Backward, given cotangent g = ∂L/∂y::

    gz = Wᵀ g                      pass-1 kernel on the cotangent
    dx = W (Aᵀ gz) + T_sparseᵀ g   pass-2 kernel with A → Aᵀ, taps
                                   flipped, offset mirrored (left → m-1-left)
    dA[c]   = Σ_b gz[b,:,c] z[b,:,c]ᵀ          gram_grad kernel
    df[c,k] = Σ_{b,j} g[b,j,c] x[b,j-k+left,c] conv_tap_grad kernel

Residual/recompute policy (backend.py docstring): residuals are the op
inputs (x, a_dense, filt) only — no O(n·r) activation is saved; the pass-1
reduction z is recomputed in the backward by one extra kernel launch.

``REPRO_PALLAS_GRAD=0`` (backend.resolve_pallas_grad) swaps the backward
to the jnp reference cotangents while keeping the Pallas forward — a
numerical-bisection escape hatch. The ``counters`` dict records which
path executed at trace time so tests (and the trainer banner) can assert
there is no silent reference fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import toeplitz
from repro.kernels import backend, ref
from repro.kernels.interp_matvec import interp_reduce_pallas
from repro.kernels.ski_fused import (ski_expand_pass2_pallas,
                                     ski_fused_pass2_pallas,
                                     ski_windowed_pass2_pallas)
from repro.kernels.ski_grad import (conv_tap_grad_pallas, gram_coef_grad_fft,
                                    gram_grad_pallas)

# trace-time instrumentation: which fwd/bwd path actually ran (tests +
# trainer banner assert on this — the whole point of PR 2 is that training
# does NOT silently fall back to the reference)
counters = {"fwd": 0, "bwd_kernel": 0, "bwd_ref": 0}


def reset_counters() -> None:
    for k in counters:
        counters[k] = 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ski_fused_tno_pallas(x, a_dense, filt, r: int, causal: bool,
                         interpret: bool):
    """y = W (A (Wᵀ x)) + T_sparse x — two kernel passes, differentiable.

    x: (b, n, d); a_dense: (d, r, r); filt: (d, m). Matches
    ref.ski_fused_tno_ref. ``interpret`` must be resolved by the caller
    (static nondiff argument).
    """
    z = interp_reduce_pallas(x, None, None, r, interpret=interpret)
    return ski_fused_pass2_pallas(x, z, a_dense, filt, causal,
                                  interpret=interpret)


def _fwd(x, a_dense, filt, r, causal, interpret):
    counters["fwd"] += 1
    y = ski_fused_tno_pallas(x, a_dense, filt, r, causal, interpret)
    return y, (x, a_dense, filt)


def _bwd_ref_formulas(x, a_dense, filt, r, causal, g):
    """jnp reference cotangents (REPRO_PALLAS_GRAD=0 escape hatch)."""
    n = x.shape[1]
    w = ref.hat_interp_matrix(n, r)                      # (n, r) constants

    def f(x_, a_, f_):
        z = jnp.einsum("nr,bnd->brd", w, x_.astype(jnp.float32)).astype(
            x_.dtype)
        return ref.ski_fused_pass2_ref(x_, z, a_, f_, causal)

    _, vjp = jax.vjp(f, x, a_dense, filt)
    return vjp(g)


def _bwd(r, causal, interpret, res, g):
    x, a_dense, filt = res
    if not backend.resolve_pallas_grad():
        counters["bwd_ref"] += 1
        return _bwd_ref_formulas(x, a_dense, filt, r, causal, g)
    counters["bwd_kernel"] += 1
    m = filt.shape[-1]
    left = 0 if causal else m // 2
    # pass 1 on the cotangent, and recomputed on the saved input
    gz = interp_reduce_pallas(g, None, None, r, interpret=interpret)
    z = interp_reduce_pallas(x, None, None, r, interpret=interpret)
    # signal cotangent: the fused pass-2 kernel as its own transposed
    # sibling — Gram transposed, taps flipped, offset mirrored
    dx = ski_fused_pass2_pallas(g, gz, jnp.swapaxes(a_dense, 1, 2),
                                jnp.flip(filt, axis=-1), causal,
                                interpret=interpret, left=m - 1 - left)
    da = gram_grad_pallas(gz, z, interpret=interpret)
    df = conv_tap_grad_pallas(g, x, m, left, interpret=interpret)
    return (dx.astype(x.dtype), da.astype(a_dense.dtype),
            df.astype(filt.dtype))


ski_fused_tno_pallas.defvjp(_fwd, _bwd)


# ------------------------------------------------ large-rank coef variants
def _gram_fft(a_coef, z):
    """z2 = A z via the length-2r circulant rfft/irfft (the FFT-Gram step
    'inside the pipeline'); z: (b, r, d)."""
    zt = jnp.swapaxes(z, 1, 2)                           # (b, d, r)
    z2t = toeplitz.toeplitz_matvec(a_coef[None], zt)
    return jnp.swapaxes(z2t, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ski_fused_tno_coef_pallas(x, a_coef, filt, r: int, causal: bool,
                              variant: str, interpret: bool):
    """Large-rank differentiable fused SKI-TNO, Toeplitz-coefficient form.

    y = W (A (Wᵀ x)) + T_sparse x with A given as a_coef (d, 2r-1) —
    never materialised dense. ``variant``:

    * "windowed" — pass 2 is the banded-W kernel streaming (bw, bw) Gram
      band blocks (ski_fused.ski_windowed_pass2_pallas).
    * "fft"      — the Gram is applied between the passes by a length-2r
      rfft/irfft circulant matvec; pass 2 is the Gram-free windowed
      expand+conv kernel (ski_fused.ski_expand_pass2_pallas).

    Matches ref.ski_fused_tno_coef_ref. ``interpret`` and ``variant``
    must be resolved by the caller (static nondiff arguments).
    """
    z = interp_reduce_pallas(x, None, None, r, interpret=interpret)
    if variant == "windowed":
        return ski_windowed_pass2_pallas(x, z, a_coef, filt, causal,
                                         interpret=interpret)
    return ski_expand_pass2_pallas(x, _gram_fft(a_coef, z), filt, causal,
                                   interpret=interpret)


def _coef_fwd(x, a_coef, filt, r, causal, variant, interpret):
    counters["fwd"] += 1
    y = ski_fused_tno_coef_pallas(x, a_coef, filt, r, causal, variant,
                                  interpret)
    return y, (x, a_coef, filt)


def _coef_bwd_ref_formulas(x, a_coef, filt, r, causal, g):
    """jnp reference cotangents (REPRO_PALLAS_GRAD=0 escape hatch)."""
    n = x.shape[1]
    w = ref.hat_interp_matrix(n, r)                      # (n, r) constants

    def f(x_, a_, f_):
        z = jnp.einsum("nr,bnd->brd", w, x_.astype(jnp.float32)).astype(
            x_.dtype)
        z2 = _gram_fft(a_, z)
        return ref.ski_expand_pass2_ref(x_, z2, f_, causal)

    _, vjp = jax.vjp(f, x, a_coef, filt)
    return vjp(g)


def _coef_bwd(r, causal, variant, interpret, res, g):
    x, a_coef, filt = res
    if not backend.resolve_pallas_grad():
        counters["bwd_ref"] += 1
        return _coef_bwd_ref_formulas(x, a_coef, filt, r, causal, g)
    counters["bwd_kernel"] += 1
    m = filt.shape[-1]
    left = 0 if causal else m // 2
    gz = interp_reduce_pallas(g, None, None, r, interpret=interpret)
    z = interp_reduce_pallas(x, None, None, r, interpret=interpret)
    # signal cotangent: transposed band — Aᵀ of a Toeplitz matrix is the
    # lag-reversed coefficient line; taps flipped, offset mirrored
    coef_t = jnp.flip(a_coef, axis=-1)
    filt_t = jnp.flip(filt, axis=-1)
    if variant == "windowed":
        dx = ski_windowed_pass2_pallas(g, gz, coef_t, filt_t, causal,
                                       interpret=interpret,
                                       left=m - 1 - left)
    else:
        dx = ski_expand_pass2_pallas(g, _gram_fft(coef_t, gz), filt_t,
                                     causal, interpret=interpret,
                                     left=m - 1 - left)
    # parameter cotangents: FFT diagonal-sum correlation (coefficient
    # form of gram_grad — the dense (d, r, r) panel must never exist)
    dcoef = gram_coef_grad_fft(gz, z)
    df = conv_tap_grad_pallas(g, x, m, left, interpret=interpret)
    return (dx.astype(x.dtype), dcoef.astype(a_coef.dtype),
            df.astype(filt.dtype))


ski_fused_tno_coef_pallas.defvjp(_coef_fwd, _coef_bwd)
