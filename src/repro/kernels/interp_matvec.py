"""Pallas TPU kernels: banded interpolation-matrix actions for SKI (§3.2.1).

Because inducing points are *uniform*, the linear-interp weight of position
i on grid node j is the hat function ``max(0, 1 - |i/h - j|)`` — so W never
needs to be materialised or gathered. Each kernel regenerates its block of
W from ``broadcasted_iota`` in VMEM and contracts it on the MXU:

* ``interp_reduce``:  z = Wᵀ x  — grid (b, d-tiles, n-tiles), accumulating
  the (r, BD) output across the sequence tiles (k-loop pattern).
* ``interp_expand``:  y = W z  — z (r ≤ 512) lives whole in VMEM.

For r ≤ 512 the dense-hat contraction (O(n r) MXU MACs) beats the O(n)
two-tap band on TPU for the same reason the paper's dense GPU path beat
sparse tensors; the asymptotic O(n) form is a windowed variant of the same
kernel (see DESIGN §3 / EXPERIMENTS §Perf for the crossover analysis).

Shape policy (repro.kernels.backend): tile sizes come from the autotune
cache / heuristic; ragged n, d are zero-padded to the tile multiple and
sliced back. The hat spacing ``h`` is always computed from the *true* n,
so padded rows get weights applied to zero inputs (reduce) or are sliced
away (expand) — both exact under linearity.

Training path (PR 2): both kernels carry ``jax.custom_vjp`` rules. W has
no trainable parameters (the hat weights are regenerated from the uniform
grid), so each backward is a single launch of the *other* kernel:
d(Wᵀx)/dx ⊢ expand, d(Wz)/dz ⊢ reduce. Residual-free — nothing is saved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend


def _hat_weights(n_start, bn, r, h, dtype=jnp.float32):
    """(bn, r) linear-interp weights for global positions n_start..+bn.
    Mosaic's iota is integer-only, so positions are built in int32 and
    converted."""
    i = (jax.lax.broadcasted_iota(jnp.int32, (bn, r), 0)
         + n_start).astype(jnp.float32)
    j = jax.lax.broadcasted_iota(jnp.int32, (bn, r), 1).astype(jnp.float32)
    return jnp.maximum(0.0, 1.0 - jnp.abs(i / h - j)).astype(dtype)


def _reduce_kernel(x_ref, o_ref, *, bn, r, h):
    ni = pl.program_id(2)
    w = _hat_weights(ni * bn, bn, r, h)               # (bn, r)
    part = jnp.dot(w.T, x_ref[0].astype(jnp.float32),
                   preferred_element_type=jnp.float32)  # (r, bd)

    @pl.when(ni == 0)
    def _init():
        o_ref[0] = part.astype(o_ref.dtype)

    @pl.when(ni > 0)
    def _acc():
        o_ref[0] = (o_ref[0].astype(jnp.float32) + part).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r", "h", "interpret", "bn", "bd"))
def _reduce_call(x, r: int, h: float, *, interpret, bn, bd):
    b, n, d = x.shape
    grid = (b, d // bd, n // bn)
    return pl.pallas_call(
        functools.partial(_reduce_kernel, bn=bn, r=r, h=h),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bn, bd), lambda bi, di, ni: (bi, ni, di))],
        out_specs=pl.BlockSpec((1, r, bd), lambda bi, di, ni: (bi, 0, di)),
        out_shape=jax.ShapeDtypeStruct((b, r, d), x.dtype),
        interpret=interpret,
    )(x)


def _expand_blocks(n, d, dtype, interpret):
    """(bn, bd) for an expand-shaped launch (cache-or-heuristic only — the
    backward rules run under tracers, so no timing sweep)."""
    bn, bd = backend.get_blocks("interp_expand", n, d, dtype, interpret)
    return backend.clamp_blocks(bn, bd, n, d, interpret)


def _reduce_blocks(n, d, dtype, interpret):
    bn, bd = backend.get_blocks("interp_reduce", n, d, dtype, interpret)
    return backend.clamp_blocks(bn, bd, n, d, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _reduce_core(x, n, r, h, interpret, bn, bd):
    return _reduce_padded(x, r, h, interpret, bn, bd)


def _reduce_core_fwd(x, n, r, h, interpret, bn, bd):
    return _reduce_core(x, n, r, h, interpret, bn, bd), None


def _reduce_core_bwd(n, r, h, interpret, bn, bd, res, g):
    del res                                           # residual-free
    if not backend.resolve_pallas_grad():
        from repro.kernels import ref
        w = ref.hat_interp_matrix(n, r)
        dx = jnp.einsum("nr,brd->bnd", w, g.astype(jnp.float32))
        return (dx.astype(g.dtype),)
    ebn, ebd = _expand_blocks(n, g.shape[2], g.dtype, interpret)
    return (_expand_padded(g, n, h, interpret, ebn, ebd),)


_reduce_core.defvjp(_reduce_core_fwd, _reduce_core_bwd)


def interp_reduce_pallas(x, idx_lo, w_lo, r: int, *, interpret=None,
                         bn=None, bd=None):
    """z = Wᵀ x. x: (b, n, d) -> (b, r, d). idx_lo/w_lo unused (weights are
    regenerated from the uniform grid); kept for oracle-parity signature.
    Differentiable in x (custom VJP: the backward is one expand launch)."""
    del idx_lo, w_lo
    b, n, d = x.shape
    interpret = backend.resolve_interpret(interpret)
    h = (n - 1) / (r - 1)                             # spacing from TRUE n
    if bn is None or bd is None:
        tune = None
        if backend.is_concrete(x):
            tune = lambda BN, BD: _reduce_padded(x, r, h, interpret, BN, BD)
        hbn, hbd = backend.get_blocks("interp_reduce", n, d, x.dtype,
                                      interpret, tune_call=tune,
                                      extra=f"r={r}")
        bn = bn or hbn
        bd = bd or hbd
    bn, bd = backend.clamp_blocks(bn, bd, n, d, interpret)
    return _reduce_core(x, n, r, h, interpret, bn, bd)


def _reduce_padded(x, r, h, interpret, bn, bd):
    b, n, d = x.shape
    np_, dp = backend.round_up(n, bn), backend.round_up(d, bd)
    if np_ != n or dp != d:
        x = jnp.pad(x, ((0, 0), (0, np_ - n), (0, dp - d)))
        return _reduce_call(x, r, h, interpret=interpret, bn=bn,
                            bd=bd)[:, :, :d]
    return _reduce_call(x, r, h, interpret=interpret, bn=bn, bd=bd)


def _expand_kernel(z_ref, o_ref, *, bn, r, h):
    ni = pl.program_id(2)
    w = _hat_weights(ni * bn, bn, r, h)               # (bn, r)
    y = jnp.dot(w, z_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)   # (bn, bd)
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "h", "interpret", "bn", "bd"))
def _expand_call(z, n: int, h: float, *, interpret, bn, bd):
    b, r, d = z.shape
    grid = (b, d // bd, n // bn)
    return pl.pallas_call(
        functools.partial(_expand_kernel, bn=bn, r=r, h=h),
        grid=grid,
        in_specs=[pl.BlockSpec((1, r, bd), lambda bi, di, ni: (bi, 0, di))],
        out_specs=pl.BlockSpec((1, bn, bd), lambda bi, di, ni: (bi, ni, di)),
        out_shape=jax.ShapeDtypeStruct((b, n, d), z.dtype),
        interpret=interpret,
    )(z)


def _expand_padded(z, n, h, interpret, bn, bd):
    b, r, d = z.shape
    np_, dp = backend.round_up(n, bn), backend.round_up(d, bd)
    if dp != d:
        z = jnp.pad(z, ((0, 0), (0, 0), (0, dp - d)))
    out = _expand_call(z, np_, h, interpret=interpret, bn=bn, bd=bd)
    return out[:, :n, :d] if (np_ != n or dp != d) else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _expand_core(z, n, r, h, interpret, bn, bd):
    return _expand_padded(z, n, h, interpret, bn, bd)


def _expand_core_fwd(z, n, r, h, interpret, bn, bd):
    return _expand_core(z, n, r, h, interpret, bn, bd), None


def _expand_core_bwd(n, r, h, interpret, bn, bd, res, g):
    del res                                           # residual-free
    if not backend.resolve_pallas_grad():
        from repro.kernels import ref
        w = ref.hat_interp_matrix(n, r)
        dz = jnp.einsum("nr,bnd->brd", w, g.astype(jnp.float32))
        return (dz.astype(g.dtype),)
    rbn, rbd = _reduce_blocks(n, g.shape[2], g.dtype, interpret)
    return (_reduce_padded(g, r, h, interpret, rbn, rbd),)


_expand_core.defvjp(_expand_core_fwd, _expand_core_bwd)


def interp_expand_pallas(z, idx_lo, w_lo, *, interpret=None, bn=None, bd=None):
    """y = W z. z: (b, r, d) -> (b, n, d) with n = idx_lo.shape[0].
    Differentiable in z (custom VJP: the backward is one reduce launch)."""
    del w_lo
    n = int(idx_lo.shape[0])
    b, r, d = z.shape
    interpret = backend.resolve_interpret(interpret)
    h = (n - 1) / (r - 1)
    if bn is None or bd is None:
        tune = None
        if backend.is_concrete(z):
            tune = lambda BN, BD: _expand_padded(z, n, h, interpret, BN, BD)
        hbn, hbd = backend.get_blocks("interp_expand", n, d, z.dtype,
                                      interpret, tune_call=tune,
                                      extra=f"r={r}")
        bn = bn or hbn
        bd = bd or hbd
    bn, bd = backend.clamp_blocks(bn, bd, n, d, interpret)
    return _expand_core(z, n, r, h, interpret, bn, bd)
