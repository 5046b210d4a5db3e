"""Fused SKI-TNO pass-2 Pallas kernel (paper §3.2, DESIGN §3 item 1).

The unfused SKI-TNO pipeline launches four kernels

    y = short_conv(x) + W · (A · (Wᵀ x))
        └── k1 ──┘       └k4┘ └k3┘ └k2┘

streaming the full (b, n, d) activation through HBM between each. This
module implements the *two-pass* fused form:

* **pass 1** — ``interp_reduce`` (kernels/interp_matvec.py): z = Wᵀ x with
  tiles VMEM-resident, output only (b, r, d).
* **pass 2** — THIS kernel: for each (batch, d-tile) the r×r inducing-Gram
  contraction z₂ = A z runs **once** on the MXU into VMEM scratch
  (``pl.when(ni == 0)``; r ≤ 512 → direct matmul, no FFT — the paper's
  observation that dense beats sparse/FFT at this size), then every
  sequence tile regenerates its hat-weight block of W, contracts W z₂ on
  the MXU, adds the m-tap short conv over the same VMEM-resident x tiles
  (halo via prev/cur/next BlockSpecs), and performs a **single** output
  write.

Net: four HBM round-trips of (b, n, d) collapse into two (read x, write y).

Ragged n, d follow the backend zero-pad policy; the hat spacing h comes
from the true n. When bn < m (tiny n) the jnp reference path is used.

Training path (PR 2): the tap offset is generalised from the causal flag
to an arbitrary ``left`` so that this same kernel serves as its own
backward sibling — dx = W (Aᵀ (Wᵀ g)) + T_sparseᵀ g is exactly this
kernel launched on the cotangent with A transposed, the taps flipped and
left mirrored to m-1-left (see kernels/ski_vjp.py for the custom VJP).

Large-rank variants (PR 3)
--------------------------
The dense-Gram kernel above pins the whole (bd, r, r) Gram per d-tile in
VMEM — a hard r ≤ 512 ceiling (and at r = 8192 the (d, r, r) HBM
materialisation itself is ~16 GB, so the dense form cannot even be built).
Two variants remove the ceiling; both consume the Gram in *Toeplitz
coefficient* form a_coef (d, 2r-1) and share the jnp oracle
``ref.ski_fused_tno_coef_ref``:

* ``ski_windowed_pass2_pallas`` — the windowed O(n) banded-W form. Each
  row of W has ≤ 2 interpolation taps, so a length-bn sequence tile only
  ever reads a window of ``bw ≈ bn/h + O(1)`` rows of z₂ = A z. The
  kernel computes exactly that window per tile, streaming the Gram as
  kb = rp/bw Toeplitz **(bw, bw) band blocks**, each applied from a
  (2bw-1) coefficient window as bw static shifted slices (no gather)
  multiply-added against the matching z chunk on the VPU. Per-tile VMEM
  is O(bd·bw) + the (2rp-1, bd) coefficient line + the (rp, bd) z tile —
  never an (r, r) panel. Total Gram MACs are b·d·r² across the grid, the
  same as the dense kernel's once-per-d-tile contraction (windows of
  adjacent tiles overlap by ≤ 2 rows).
* ``ski_expand_pass2_pallas`` — the Gram-free second pass for the
  FFT-Gram variant: z₂ = A z is applied *outside* (rfft/irfft circulant
  matvec, O(r log r) — see ski_vjp) and this kernel fuses the windowed
  hat-weight expansion of z₂ with the short conv and the single output
  write. Used when r is beyond the windowed band budget, where the
  O(r²/n) per-row band work loses to O(r log r / r) FFT work.

The backward of both is the same kernel with the coefficients flipped
(Aᵀ of a Toeplitz matrix = lag-reversed coefficients), the taps flipped
and left mirrored — the "transposed band" of ISSUE 3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend
from repro.kernels.interp_matvec import _hat_weights


def _halo_window(prev_ref, cur_ref, nxt_ref, *, m, left, bn, nb_total, ni):
    """(bn + m - 1, bd) sequence window assembled from halo'd
    prev/cur/next VMEM tiles, boundary tiles zero-masked. The single
    definition of the conv halo semantics — used by the forward conv of
    every pass-2 kernel here AND by its transposed sibling
    ``ski_grad._tap_grad_kernel`` (which must window identically)."""
    hl = m - 1 - left
    hr = left
    prev = jnp.where(ni > 0, prev_ref[0], jnp.zeros_like(prev_ref[0]))
    nxt = jnp.where(ni < nb_total - 1, nxt_ref[0], jnp.zeros_like(nxt_ref[0]))
    cur = cur_ref[0]
    return jnp.concatenate([prev[bn - hl:], cur] + ([nxt[:hr]] if hr else []),
                           axis=0) if hl else jnp.concatenate(
                               [cur] + ([nxt[:hr]] if hr else []), axis=0)


def _conv_halo_acc(prev_ref, cur_ref, nxt_ref, filt_ref, acc, *,
                   m, left, bn, nb_total, ni):
    """Add the m-tap short conv over halo'd prev/cur/next VMEM tiles (VPU)
    into ``acc`` (bn, bd) — the sparse half shared by every pass-2 kernel."""
    xwin = _halo_window(prev_ref, cur_ref, nxt_ref, m=m, left=left, bn=bn,
                        nb_total=nb_total, ni=ni)
    f = filt_ref[...].astype(jnp.float32)                # (bd, m)
    for k in range(m):
        sl = xwin[(m - 1 - k):(m - 1 - k) + bn].astype(jnp.float32)
        acc = acc + sl * f[:, k][None, :]
    return acc


def _fused_kernel(prev_ref, cur_ref, nxt_ref, z_ref, a_ref, filt_ref, o_ref,
                  z2_ref, *, m, left, bn, r, h, nb_total):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _gram():
        # z2 = A z once per (batch, d-tile): batched (bd) r x r MXU matvec
        zt = z_ref[0].astype(jnp.float32).T              # (bd, r)
        a = a_ref[...].astype(jnp.float32)               # (bd, r, r)
        z2 = jax.lax.dot_general(a, zt, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        z2_ref[...] = z2.T                               # (r, bd)

    # low-rank half: y_low = W_tile z2 (MXU)
    w = _hat_weights(ni * bn, bn, r, h)                  # (bn, r)
    acc = jnp.dot(w, z2_ref[...], preferred_element_type=jnp.float32)
    acc = _conv_halo_acc(prev_ref, cur_ref, nxt_ref, filt_ref, acc,
                         m=m, left=left, bn=bn, nb_total=nb_total, ni=ni)
    o_ref[0] = acc.astype(o_ref.dtype)                   # single write


@functools.partial(jax.jit,
                   static_argnames=("left", "h", "interpret", "bn", "bd"))
def _fused_call(x, z, a_dense, filt, left: int, h: float, *,
                interpret, bn, bd):
    """Requires n % bn == 0, d % bd == 0, bn >= m (padded by the wrapper)."""
    b, n, d = x.shape
    r = z.shape[1]
    m = filt.shape[-1]
    nb, db = n // bn, d // bd
    grid = (b, db, nb)

    def xmap(shift):
        def f(bi, di, ni):
            return (bi, jnp.clip(ni + shift, 0, nb - 1), di)
        return f

    return pl.pallas_call(
        functools.partial(_fused_kernel, m=m, left=left, bn=bn, r=r, h=h,
                          nb_total=nb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, bd), xmap(-1)),
            pl.BlockSpec((1, bn, bd), xmap(0)),
            pl.BlockSpec((1, bn, bd), xmap(+1)),
            pl.BlockSpec((1, r, bd), lambda bi, di, ni: (bi, 0, di)),
            pl.BlockSpec((bd, r, r), lambda bi, di, ni: (di, 0, 0)),
            pl.BlockSpec((bd, m), lambda bi, di, ni: (di, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, bd), lambda bi, di, ni: (bi, ni, di)),
        out_shape=jax.ShapeDtypeStruct((b, n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((r, bd), jnp.float32)],
        interpret=interpret,
    )(x, x, x, z, a_dense, filt)


def _padded_call(x, z, a_dense, filt, left, h, interpret, bn, bd):
    b, n, d = x.shape
    np_, dp = backend.round_up(n, bn), backend.round_up(d, bd)
    if np_ != n or dp != d:
        pd = dp - d
        xp = jnp.pad(x, ((0, 0), (0, np_ - n), (0, pd)))
        zp = jnp.pad(z, ((0, 0), (0, 0), (0, pd)))
        ap = jnp.pad(a_dense, ((0, pd), (0, 0), (0, 0)))
        fp = jnp.pad(filt, ((0, pd), (0, 0)))
        return _fused_call(xp, zp, ap, fp, left, h, interpret=interpret,
                           bn=bn, bd=bd)[:, :n, :d]
    return _fused_call(x, z, a_dense, filt, left, h, interpret=interpret,
                       bn=bn, bd=bd)


def ski_fused_pass2_pallas(x, z, a_dense, filt, causal: bool, *,
                           interpret=None, bn=None, bd=None, left=None):
    """y = W (A z) + T_sparse x, one kernel, one output write.

    x: (b, n, d); z = Wᵀx: (b, r, d); a_dense: (d, r, r) per-channel Gram;
    filt: (d, m). Matches ref.ski_fused_pass2_ref. ``left`` overrides the
    causal-derived tap offset (backward-sibling launches only).
    """
    b, n, d = x.shape
    m = filt.shape[-1]
    if left is None:
        left = 0 if causal else m // 2
    interpret = backend.resolve_interpret(interpret)
    h = (n - 1) / (z.shape[1] - 1)
    if bn is None or bd is None:
        tune = None
        if backend.is_concrete(x, z, a_dense, filt):
            tune = lambda BN, BD: _padded_call(x, z, a_dense, filt, left,
                                               h, interpret, BN, BD)
        hbn, hbd = backend.get_blocks("ski_fused", n, d, x.dtype, interpret,
                                      tune_call=tune,
                                      extra=f"r={z.shape[1]}|m={m}")
        bn = bn or hbn
        bd = bd or hbd
    bn, bd = backend.clamp_blocks(bn, bd, n, d, interpret)
    if bn < m:
        from repro.kernels import ref
        return ref.ski_fused_pass2_ref(x, z, a_dense, filt, causal, left=left)
    return _padded_call(x, z, a_dense, filt, left, h, interpret, bn, bd)


# ---------------------------------------------------- large-rank variants
def _windowed_kernel(prev_ref, cur_ref, nxt_ref, z_ref, *rest, m, left, bn,
                     w0_max, bw, h, nb_total, banded):
    if banded:
        ac_ref, filt_ref, o_ref = rest
    else:
        filt_ref, o_ref = rest
    ni = pl.program_id(2)
    s = ni * bn
    # first inducing column touched by this tile's hat rows, clamped so the
    # static-width window stays inside the (padded) inducing grid
    w0 = jnp.clip(jnp.floor(s.astype(jnp.float32) / h).astype(jnp.int32),
                  0, w0_max)

    if banded:
        # z2 window = A[w0:w0+bw, :] z with A[s, t] = ac[rp-1 + s - t]
        # (ac: the lag line padded to rank rp, lags on sublanes), streamed
        # over kb chunks of bw inducing columns. Windows are sliced on the
        # refs: Mosaic lowers no dynamic_slice of a loaded value, and a
        # dynamic offset only on the sublane axis.
        bd = ac_ref.shape[1]
        rp = z_ref.shape[1]
        kb = rp // bw

        def body(k, acc):
            base = rp - bw + w0 - k * bw
            win = ac_ref[pl.ds(base, 2 * bw - 1), :].astype(jnp.float32)
            zc = z_ref[0, pl.ds(pl.multiple_of(k * bw, 8), bw), :].astype(
                jnp.float32)                             # (bw, bd)
            # A[w0+j, k*bw+u] = win[bw-1-u+j]: bw static shifted slices of
            # the (2bw-1) window — no gather
            for u in range(bw):
                acc = acc + win[bw - 1 - u:2 * bw - 1 - u] * zc[u:u + 1]
            return acc

        z2w = jax.lax.fori_loop(0, kb, body,
                                jnp.zeros((bw, bd), jnp.float32))  # (bw, bd)
    else:
        # FFT-Gram variant: z_ref already holds z2 = A z; just window it
        z2w = z_ref[0, pl.ds(w0, bw), :].astype(jnp.float32)   # (bw, bd)

    # windowed hat-weight expansion: w[i, j] = hat((s+i)/h - (w0+j)) (MXU)
    i = (jax.lax.broadcasted_iota(jnp.int32, (bn, bw), 0)
         + s).astype(jnp.float32)
    j = (jax.lax.broadcasted_iota(jnp.int32, (bn, bw), 1)
         + w0).astype(jnp.float32)
    wwin = jnp.maximum(0.0, 1.0 - jnp.abs(i / h - j))
    acc = jnp.dot(wwin, z2w, preferred_element_type=jnp.float32)
    acc = _conv_halo_acc(prev_ref, cur_ref, nxt_ref, filt_ref, acc,
                         m=m, left=left, bn=bn, nb_total=nb_total, ni=ni)
    o_ref[0] = acc.astype(o_ref.dtype)                   # single write


@functools.partial(jax.jit, static_argnames=(
    "left", "h", "w0_max", "banded", "interpret", "bn", "bd", "bw"))
def _windowed_call(x, z, ac, filt, left: int, h: float, w0_max: int, *,
                   banded, interpret, bn, bd, bw):
    """Requires n % bn == 0, d % bd == 0, bn >= m, z rows padded to rp
    (a multiple of bw when banded) — all arranged by _windowed_padded."""
    b, n, d = x.shape
    rp = z.shape[1]
    m = filt.shape[-1]
    nb, db = n // bn, d // bd
    grid = (b, db, nb)

    def xmap(shift):
        def f(bi, di, ni):
            return (bi, jnp.clip(ni + shift, 0, nb - 1), di)
        return f

    in_specs = [
        pl.BlockSpec((1, bn, bd), xmap(-1)),
        pl.BlockSpec((1, bn, bd), xmap(0)),
        pl.BlockSpec((1, bn, bd), xmap(+1)),
        pl.BlockSpec((1, rp, bd), lambda bi, di, ni: (bi, 0, di)),
    ]
    args = [x, x, x, z]
    if banded:
        in_specs.append(pl.BlockSpec((2 * rp - 1, bd),
                                     lambda bi, di, ni: (0, di)))
        args.append(ac)
    in_specs.append(pl.BlockSpec((bd, m), lambda bi, di, ni: (di, 0)))
    args.append(filt)

    return pl.pallas_call(
        functools.partial(_windowed_kernel, m=m, left=left, bn=bn,
                          w0_max=w0_max, bw=bw, h=h, nb_total=nb,
                          banded=banded),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bn, bd), lambda bi, di, ni: (bi, ni, di)),
        out_shape=jax.ShapeDtypeStruct((b, n, d), x.dtype),
        interpret=interpret,
    )(*args)


def _windowed_padded(x, z, a_coef, filt, left, h, r, banded, interpret,
                     bn, bd, bw):
    b, n, d = x.shape
    # rp: multiple of bw (banded chunk loop) or of the sublane unit
    rp = backend.round_up(r, bw) if banded else max(backend.round_up(r, 8), bw)
    np_, dp = backend.round_up(n, bn), backend.round_up(d, bd)
    w0_max = max(0, r - bw)
    if np_ != n or dp != d:
        x = jnp.pad(x, ((0, 0), (0, np_ - n), (0, dp - d)))
        filt = jnp.pad(filt, ((0, dp - d), (0, 0)))
    if rp != r or dp != d:
        z = jnp.pad(z, ((0, 0), (0, rp - r), (0, dp - d)))
    ac = None
    if banded:
        # lags symmetric-padded to rank rp (extra |lag| >= r coefficients
        # are zero, so padded z rows / window rows contribute exactly
        # nothing) and laid out (2rp-1, d): lags on sublanes
        ac = jnp.pad(a_coef, ((0, dp - d), (rp - r, rp - r))).T
    out = _windowed_call(x, z, ac, filt, left, h, w0_max, banded=banded,
                         interpret=interpret, bn=bn, bd=bd, bw=bw)
    return out[:, :n, :d]


def _coef_ref_fallback(x, z2_or_z, a_coef, filt, causal, left):
    from repro.kernels import ref
    if a_coef is not None:
        z2 = ref.toeplitz_gram_matvec_ref(a_coef, z2_or_z)
    else:
        z2 = z2_or_z
    return ref.ski_expand_pass2_ref(x, z2, filt, causal, left=left)


def _windowed_wrapper(x, z, a_coef, filt, causal, banded, interpret,
                      bn, bd, bw, left):
    """Shared block/band resolution + tiny-shape fallback for the two
    large-rank pass-2 wrappers."""
    b, n, d = x.shape
    r = z.shape[1]
    m = filt.shape[-1]
    if left is None:
        left = 0 if causal else m // 2
    interpret = backend.resolve_interpret(interpret)
    if r < 2:
        return _coef_ref_fallback(x, z, a_coef, filt, causal, left)
    h = (n - 1) / (r - 1)
    kern = "ski_windowed" if banded else "ski_expand2"
    if bn is None or bd is None:
        tune = None
        if backend.is_concrete(x, z, filt) and (
                a_coef is None or backend.is_concrete(a_coef)):
            def tune(BN, BD):
                BN, BW = backend.band_fit(BN, n, r)
                return _windowed_padded(x, z, a_coef, filt, left, h, r,
                                        banded, interpret, BN, BD, BW)
        hbn, hbd = backend.get_blocks(kern, n, d, x.dtype, interpret,
                                      tune_call=tune, extra=f"r={r}|m={m}")
        bn = bn or hbn
        bd = bd or hbd
    bn, bd = backend.clamp_blocks(bn, bd, n, d, interpret)
    if bw is None:
        bn, bw = backend.band_fit(bn, n, r)
    if bn < m:
        return _coef_ref_fallback(x, z, a_coef, filt, causal, left)
    return _windowed_padded(x, z, a_coef, filt, left, h, r, banded,
                            interpret, bn, bd, bw)


def ski_windowed_pass2_pallas(x, z, a_coef, filt, causal: bool, *,
                              interpret=None, bn=None, bd=None, bw=None,
                              left=None):
    """Windowed O(n) banded-W pass 2: y = W (A z) + T_sparse x, with the
    Gram consumed in Toeplitz-coefficient form and streamed as (bw, bw)
    band blocks per sequence tile — no (r, r) panel ever exists, in VMEM
    or HBM.

    x: (b, n, d); z = Wᵀx: (b, r, d); a_coef: (d, 2r-1) lags -(r-1)..r-1;
    filt: (d, m). Matches ref.ski_fused_tno_coef_ref's pass 2 (i.e.
    toeplitz_gram_matvec_ref + ski_expand_pass2_ref). ``left`` overrides
    the causal-derived tap offset; the backward sibling is this same
    kernel with ``a_coef`` lag-flipped (transposed band), taps flipped
    and left mirrored.
    """
    return _windowed_wrapper(x, z, a_coef, filt, causal, True, interpret,
                             bn, bd, bw, left)


def ski_expand_pass2_pallas(x, z2, filt, causal: bool, *, interpret=None,
                            bn=None, bd=None, bw=None, left=None):
    """Gram-free windowed pass 2 for the FFT-Gram variant: y = W z2 +
    T_sparse x where z2 = A z was applied outside via rfft/irfft.

    x: (b, n, d); z2: (b, r, d); filt: (d, m). Matches
    ref.ski_expand_pass2_ref. Same windowed hat-weight expansion as the
    banded kernel — each tile reads only its (bw, bd) window of z2.
    """
    return _windowed_wrapper(x, z2, None, filt, causal, False, interpret,
                             bn, bd, bw, left)
