"""jit'd dispatch wrappers over the Pallas kernels and their jnp oracles.

Dispatch policy (implemented in :mod:`repro.kernels.backend`)
-------------------------------------------------------------
Every op takes ``use_pallas`` / ``interpret`` keywords; ``None`` (the
default) defers to the global policy, resolved in this order:

1. **Explicit argument** — ``use_pallas=True/False`` per call site wins.
2. **Programmatic default** — :func:`set_default_backend`.
3. **Environment** — ``REPRO_USE_PALLAS`` = "1" / "0" / "auto" (default
   "auto"); ``REPRO_PALLAS_INTERPRET`` = "1" / "0" / "auto".
4. **Platform detection** — under "auto", the Pallas path (and compiled,
   non-interpret execution) is selected exactly when
   ``jax.default_backend() == "tpu"``; on CPU/GPU the jnp reference
   oracles run, and any forced Pallas call uses interpret mode.

Training (PR 2): the Pallas wrappers carry ``jax.custom_vjp`` rules whose
backwards are themselves kernel launches (custom_vjp bypasses the
pallas_call autodiff limitation, so this holds in interpret mode too) —
``jax.grad`` through any op here stays on whichever path the forward
dispatched to. ``REPRO_PALLAS_GRAD`` = "0" forces the jnp reference
cotangent formulas under a Pallas forward (debugging escape hatch).

Block sizes are *not* hardcoded: each kernel wrapper asks
``backend.get_blocks(kernel, n, d, dtype, platform, mode)``, which
consults an **on-disk autotune cache** (``REPRO_AUTOTUNE_CACHE``, default
``~/.cache/repro/autotune.json``), runs a timing sweep on miss when
``REPRO_AUTOTUNE=1`` and the inputs are concrete, and otherwise falls back
to a shape-fitted heuristic. Ragged n / d (not multiples of the tile) are
zero-padded to the tile boundary and sliced back — padding is
semantics-preserving for every kernel here (zero-boundary conv, linear
interp/Gram contractions). Shapes too small to tile legally (e.g. n
smaller than the conv filter) fall back to the reference path instead of
asserting.
"""
from __future__ import annotations

from repro.kernels import backend, ref
from repro.obs import devstats as obs_devstats


def set_default_backend(use_pallas: bool | None) -> None:
    """Force the global Pallas/reference default (None = platform auto)."""
    backend.set_default_use_pallas(use_pallas)


def short_conv(x, filt, causal: bool, *, use_pallas=None, interpret=None):
    """Depthwise short conv — the m-tap sparse Toeplitz component.

    x (b, n, d) fp32/bf16; filt (d, m) per-channel taps; returns
    (b, n, d) in x's dtype. ``causal=True`` convolves lags 0..m-1
    (zero left boundary), ``False`` centres the taps. Oracle:
    ref.short_conv_ref; the Pallas kernel tiles the sequence with an
    (m-1)-halo. Backward: flipped taps + mirrored offset for the signal,
    ``conv_tap_grad`` correlation for the taps."""
    with obs_devstats.kernel_region("short_conv"):
        if backend.resolve_use_pallas(use_pallas):
            from repro.kernels import short_conv as k
            return k.short_conv_pallas(x, filt, causal, interpret=interpret)
        return ref.short_conv_ref(x, filt, causal)


def interp_reduce(x, idx_lo, w_lo, r: int, *, use_pallas=None, interpret=None):
    """z = Wᵀ x — project n positions onto r inducing points.

    x (b, n, d) fp32/bf16; idx_lo (n,) int32 lower-neighbour indices and
    w_lo (n,) weights describe the banded linear-interp W (reference
    path only — the Pallas kernel regenerates the hat weights in VMEM
    from the uniform grid); returns (b, r, d) in x's dtype. Oracle:
    ref.interp_reduce_ref. Backward: one :func:`interp_expand` launch
    (W is linear, so the adjoint is the sibling kernel)."""
    with obs_devstats.kernel_region("interp_reduce"):
        if backend.resolve_use_pallas(use_pallas):
            from repro.kernels import interp_matvec as k
            return k.interp_reduce_pallas(x, idx_lo, w_lo, r,
                                          interpret=interpret)
        return ref.interp_reduce_ref(x, idx_lo, w_lo, r)


def interp_expand(z, idx_lo, w_lo, *, use_pallas=None, interpret=None):
    """y = W z — interpolate r inducing values back to n positions.

    z (b, r, d) fp32/bf16; idx_lo (n,) int32 / w_lo (n,) as in
    :func:`interp_reduce` (n is read off idx_lo); returns (b, n, d) in
    z's dtype. Oracle: ref.interp_expand_ref. Backward: one
    :func:`interp_reduce` launch."""
    with obs_devstats.kernel_region("interp_expand"):
        if backend.resolve_use_pallas(use_pallas):
            from repro.kernels import interp_matvec as k
            return k.interp_expand_pallas(z, idx_lo, w_lo,
                                          interpret=interpret)
        return ref.interp_expand_ref(z, idx_lo, w_lo)


def ski_fused_pass2(x, z, a_dense, filt, causal: bool, *, use_pallas=None,
                    interpret=None):
    """Fused SKI pass 2: y = W (A z) + T_sparse x in one kernel / one write.

    x (b,n,d); z = Wᵀx (b,r,d); a_dense (d,r,r); filt (d,m). Together with
    :func:`interp_reduce` (pass 1) this is the two-pass fused SKI-TNO
    pipeline — see kernels/ski_fused.py. Forward-only on the Pallas path
    (z is an already-materialised intermediate); the trainable form is
    :func:`ski_fused_tno`.
    """
    with obs_devstats.kernel_region("ski_fused"):
        if backend.resolve_use_pallas(use_pallas):
            from repro.kernels import ski_fused as k
            return k.ski_fused_pass2_pallas(x, z, a_dense, filt, causal,
                                            interpret=interpret)
        return ref.ski_fused_pass2_ref(x, z, a_dense, filt, causal)


def ski_fused_tno(x, a_dense, filt, idx_lo, w_lo, r: int, causal: bool, *,
                  use_pallas=None, interpret=None):
    """Differentiable two-pass fused SKI-TNO: y = W (A (Wᵀ x)) + T_sparse x.

    x (b,n,d); a_dense (d,r,r) per-channel inducing Gram; filt (d,m);
    idx_lo/w_lo: inducing geometry (ref path only — the Pallas kernels
    regenerate the hat weights from the uniform grid). This is the op the
    TNN block trains through: on the Pallas path it carries a custom VJP
    whose backward is itself kernel launches (kernels/ski_vjp.py), so
    ``jax.grad`` stays at kernel speed instead of silently needing the
    reference; on the reference path plain autodiff applies. The
    ``REPRO_PALLAS_GRAD`` knob (kernels/backend.py) can force the
    reference cotangent formulas under the Pallas forward for debugging.
    """
    with obs_devstats.kernel_region("ski_fused"):
        if backend.resolve_use_pallas(use_pallas):
            from repro.kernels import ski_vjp as k
            return k.ski_fused_tno_pallas(x, a_dense, filt, int(r),
                                          bool(causal),
                                          backend.resolve_interpret(interpret))
        return ref.ski_fused_tno_ref(x, a_dense, filt, idx_lo, w_lo, r,
                                     causal)


def ski_fused_tno_coef(x, a_coef, filt, idx_lo, w_lo, r: int, causal: bool,
                       variant: str = "windowed", *, use_pallas=None,
                       interpret=None):
    """Large-rank differentiable fused SKI-TNO, coefficient-form Gram.

    x (b,n,d); a_coef (d,2r-1) Toeplitz lags of the inducing Gram (the
    dense (d,r,r) form is never materialised — 16 GB at r=8192, d=64);
    filt (d,m); idx_lo/w_lo: inducing geometry (ref path only). ``variant``
    is "windowed" (banded-W kernel streaming (bw,bw) Gram band blocks) or
    "fft" (rfft/irfft circulant Gram between the passes) — pick via
    ``backend.ski_rank_variant``. Both execution strategies compute the
    same operator and share the oracle ref.ski_fused_tno_coef_ref; the
    Pallas path carries a custom VJP whose signal cotangent is the same
    windowed kernel with the band transposed (coefficients lag-flipped)
    and the conv offset mirrored (kernels/ski_vjp.py).
    """
    with obs_devstats.kernel_region(f"ski_{variant}"):
        if backend.resolve_use_pallas(use_pallas):
            from repro.kernels import ski_vjp as k
            return k.ski_fused_tno_coef_pallas(
                x, a_coef, filt, int(r), bool(causal), str(variant),
                backend.resolve_interpret(interpret))
        return ref.ski_fused_tno_coef_ref(x, a_coef, filt, idx_lo, w_lo, r,
                                          causal)


def fd_tno(x, khat_real, *, use_pallas=None, interpret=None):
    """Differentiable causal FD-TNO (paper §3.3, Algorithm 2): one op for
    Hilbert-completed spectrum + per-channel spectral multiply + (i)rfft
    staging.

    x (b, n, d); khat_real (d, n+1) — the RPE's raw real frequency
    response on the rfft grid (no decay bias). On the Pallas path the
    signal's rfft → multiply → irfft runs, where 2n factors
    (``fd_fused.mxu_split``), as one MXU-DFT kernel per direction with its
    stages in VMEM, else as XLA FFTs around blocked Pallas kernels; the
    kernel side's lag window is a Pallas kernel between XLA FFTs
    (kernels/fd_fused.py). The op carries a custom VJP whose signal
    cotangent is the forward pipeline with the spectrum conjugated
    (causal ⇄ anticausal) — so ``jax.grad`` of a causal FD
    block stays on the kernel path, same contract as :func:`ski_fused_tno`
    (counters in fd_fused assert no silent ref fallback). On the
    reference path plain autodiff through ref.fd_tno_ref applies.
    """
    with obs_devstats.kernel_region("fd_tno"):
        if backend.resolve_use_pallas(use_pallas):
            from repro.kernels import fd_fused as k
            return k.fd_tno_pallas(x, khat_real,
                                   backend.resolve_interpret(interpret))
        return ref.fd_tno_ref(x, khat_real)


def ssd_scan(x, dt, a, b, c, d_skip, *, chunk=64, hshard=None):
    """Mamba-2 SSD chunked scan (the model-zoo state-space mixer).

    x (bt, n, h, p) fp32/bf16 per-head inputs; dt (bt, n, h) positive
    step sizes; a (h,) negative decay rates; b/c (bt, n, g, s) in/out
    projections (g groups, s state dim); d_skip (h,) skip; returns
    (bt, n, h, p). Sequential-recurrence oracle: ref.ssd_scan_ref. One
    path on every platform, differentiable by plain autodiff: the chunked
    intra/inter-state formulation in XLA (kernels/ssd_chunked.py) with
    ``chunk``-length blocks. On a TPU v5e at granite-4.0-h-micro's
    training shapes it beat a Pallas forward kernel under a custom VJP,
    forward and with its backward (PERF.md §6). ``hshard`` re-asserts
    head-axis TP sharding on the chunk-state carry (see ssd_chunked)."""
    from repro.kernels import ssd_chunked
    with obs_devstats.kernel_region("ssd"):
        return ssd_chunked.ssd_scan_chunked(x, dt, a, b, c, d_skip,
                                            chunk=chunk, hshard=hshard)
