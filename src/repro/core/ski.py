"""Sparse + low-rank TNO via asymmetric SKI (paper §3.2, Algorithm 1).

``T ≈ T_sparse + W A W^T`` where

* ``T_sparse`` (m non-zero diagonals) acts as a per-channel short 1-D conv;
* ``A`` is the r x r inducing-point Gram matrix of the warped-interp kernel
  ``k_l(t) = RPE_l(sign(t) λ^|t|)`` — itself Toeplitz because inducing
  points are uniform, so its action is an O(r log r) FFT matvec (we use a
  direct small matmul below r=512: MXU-friendlier, see DESIGN §3);
* ``W`` is the banded linear-interpolation matrix (≤2 non-zeros/row),
  applied in O(n) (Pallas kernel on TPU; scatter/gather oracle elsewhere).

Total: O(n + r log r) — the paper's mathematical complexity, which their
PyTorch implementation could not reach (sparse-tensor reshape overhead);
the TPU port does (DESIGN §3 item 1).

Execution pipeline (default, ``SKIConfig.fused=True``): the **two-pass
fused** form — pass 1 ``interp_reduce`` (z = Wᵀx), pass 2 one kernel
fusing the Gram contraction, the interp expansion and the short conv
with a single output write (kernels/ski_fused.py) — exposed as a single
differentiable op whose Pallas backward is itself kernel launches
(kernels/ski_vjp.py), so training takes the same path as inference.
How the Gram is applied is ``backend.ski_rank_variant``'s call (PR 3):

* ``dense``    (r ≤ 512, Gram under 64 MB) — ``ops.ski_fused_tno``, the
  whole (d, r, r) Gram VMEM-resident per d-tile;
* ``windowed`` (≤ 4096) — ``ops.ski_fused_tno_coef``, the O(n) banded-W
  kernel streaming (bw, bw) Toeplitz band blocks from the (d, 2r-1)
  coefficients (the dense Gram is never materialised);
* ``fft``      (beyond) — same op, Gram applied by a length-2r
  rfft/irfft circulant matvec between the two passes.

The 4-kernel unfused form remains behind ``fused=False``, which only
tests select; its Pallas ops are individually custom-VJP'd.

Forward-invariant pieces (inducing geometry, warped lag grid, Gram
coefficients / dense Gram) are grouped in a :func:`ski_plan`, built once
per layer per forward by core/block.py — not rebuilt per op — and the
param-independent grids are additionally memoised process-wide.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import toeplitz
from repro.core.rpe import (InterpRPEConfig, interp_rpe_apply,
                            interp_rpe_init)
from repro.kernels import backend, ops
from repro.nn.params import KeyGen, boxed


@dataclasses.dataclass(frozen=True)
class SKIConfig:
    d: int                    # channels
    rank: int = 64            # r inducing points
    filter_size: int = 32     # m sparse diagonals
    lam: float = 0.99         # inverse-time-warp decay
    grid_size: int = 129      # interp-RPE grid nodes on [-1,1]
    use_pallas: bool | None = None
    fused: bool = True        # two-pass fused pipeline (False: 4 kernels)


@functools.lru_cache(maxsize=128)
def _make_inducing_host(n: int, r: int):
    """Host-numpy body of :func:`make_inducing`.

    Cached as HOST numpy, not jax.Arrays: an lru_cache keyed only on
    (n, r) that holds device buffers pins them to whatever backend was
    active at first call — stale (or dead) buffers leak across
    backend/device switches (the PR 3 fix of core/fd._omega_grid, applied
    here too). Callers device_put via jnp.asarray, free under jit."""
    h = (n - 1) / (r - 1)
    f = np.arange(n, dtype=np.float32) / np.float32(h)
    lo = np.clip(np.floor(f).astype(np.int32), 0, r - 2)
    # clamp: fp32 rounding of the irrational spacing h can push the
    # boundary weight a few ulp outside [0, 1]
    w_lo = np.clip((1.0 - (f - lo.astype(np.float32))).astype(np.float32),
                   np.float32(0.0), np.float32(1.0))
    return lo, w_lo, h


def make_inducing(n: int, r: int):
    """Uniform inducing points on [0, n-1]; returns (idx_lo, w_lo, h).
    Memoised (host-side): the geometry depends only on (n, r), so all
    layers of a model (and every forward) share one copy instead of
    rebuilding it per block."""
    lo, w_lo, h = _make_inducing_host(int(n), int(r))
    return jnp.asarray(lo), jnp.asarray(w_lo), h


@functools.lru_cache(maxsize=128)
def _warped_lag_grid_host(r: int, h: float, lam: float) -> np.ndarray:
    """Host-numpy warped lags x(t) = sign(t) λ^|t| at lags -(r-1)h..(r-1)h
    — param-independent, shared across layers/forwards. Same host-cache
    policy as :func:`_make_inducing_host` (no pinned device buffers)."""
    lag = np.arange(-(r - 1), r, dtype=np.float32) * np.float32(h)
    return (np.sign(lag) *
            np.power(np.float32(lam), np.abs(lag))).astype(np.float32)


def _warped_lag_grid(r: int, h: float, lam: float) -> jax.Array:
    """Device view of the cached host grid (matches
    rpe.inverse_time_warp on the same lags)."""
    return jnp.asarray(_warped_lag_grid_host(int(r), float(h), float(lam)))


def ski_init(key, cfg: SKIConfig):
    kg = KeyGen(key)
    rpe = interp_rpe_init(kg(), InterpRPEConfig(cfg.d, cfg.grid_size))
    filt = boxed(kg(), (cfg.d, cfg.filter_size), ("tno_channel", None),
                 "normal", scale=0.02)
    return {"rpe": rpe, "filt": filt}


def inducing_gram_coeffs(params, cfg: SKIConfig, r: int, h: float):
    """(d, 2r-1) Toeplitz coefficients of A at warped inducing lags."""
    x = _warped_lag_grid(int(r), float(h), float(cfg.lam))
    vals = interp_rpe_apply(params["rpe"], InterpRPEConfig(cfg.d, cfg.grid_size), x)
    return vals.T  # (d, 2r-1)


def fused_eligible(cfg: SKIConfig, r: int) -> bool:
    """Dense-Gram eligibility (kept for back-compat; the full policy is
    backend.ski_rank_variant — with the large-rank variants every rank is
    fused-eligible, this only says whether the *dense* kernel serves it)."""
    return cfg.fused and backend.ski_rank_variant(r, cfg.d) == "dense"


def ski_plan(params, cfg: SKIConfig, n: int, causal: bool = False,
             variant: str | None = None) -> dict:
    """Precompute everything that is invariant across ops within a forward:
    inducing geometry, Gram coefficients, the Gram variant decision, and
    (dense variant only) the dense per-channel Gram. Built once per layer
    per forward (core/block.py); serving can additionally reuse it across
    decode steps of equal n.

    ``variant`` — optional override of ``backend.ski_rank_variant``
    ("dense" | "windowed" | "fft"); used by the variant-parity tests and
    the large-r benchmark to pin a strategy at a rank the policy would
    route elsewhere. The override is UNCHECKED: forcing "dense" builds
    the (d, r, r) Gram regardless of the byte budget (that is the point —
    the benchmark times the dense arm past the policy ceiling), so the
    caller owns the memory math at large r.
    """
    r = min(cfg.rank, n)
    idx_lo, w_lo, h = make_inducing(n, r)
    a_coef = inducing_gram_coeffs(params, cfg, r, h)            # (d, 2r-1)
    if causal:
        a_coef = toeplitz.causal_mask_coeffs(a_coef, r)
    if variant is None:
        variant = backend.ski_rank_variant(r, cfg.d) if cfg.fused \
            else "unfused"
    plan = {"r": r, "h": h, "idx_lo": idx_lo, "w_lo": w_lo,
            "causal": causal, "a_coef": a_coef, "variant": variant}
    if variant == "dense":
        plan["a_dense"] = toeplitz.dense_toeplitz(a_coef, r)    # (d, r, r)
    return plan


def ski_tno_apply(params, cfg: SKIConfig, x: jax.Array,
                  causal: bool = False, plan: dict | None = None) -> jax.Array:
    """x: (b, n, d) -> (b, n, d). Bidirectional by default (paper trains
    SKI only bidirectionally; the causal flag exists for the Appendix-B
    negative-result benchmark via core.causal_ski).

    ``plan`` — optional precomputed :func:`ski_plan` (must have been built
    with the same ``causal`` flag); computed here when absent.
    """
    b, n, d = x.shape
    if plan is None:
        plan = ski_plan(params, cfg, n, causal)
    # a stale plan (wrong masking or sequence length) silently computes a
    # different operator — reject it here rather than return wrong numbers
    if plan["causal"] != causal or plan["idx_lo"].shape[0] != n:
        raise ValueError(
            f"plan mismatch: built for causal={plan['causal']}, "
            f"n={plan['idx_lo'].shape[0]}; called with causal={causal}, n={n}")
    r, idx_lo, w_lo = plan["r"], plan["idx_lo"], plan["w_lo"]
    variant = plan.get("variant",
                       "dense" if "a_dense" in plan else "unfused")

    if variant == "dense" and "a_dense" in plan:
        # two-pass fused pipeline as ONE differentiable op: on the Pallas
        # path this is the custom-VJP kernel pair (kernels/ski_vjp.py), so
        # jax.grad through a TNN block trains at kernel speed instead of
        # silently requiring the reference (ROADMAP "Compiled-TPU status")
        y = ops.ski_fused_tno(x, plan["a_dense"], params["filt"],
                              idx_lo, w_lo, r, causal,
                              use_pallas=cfg.use_pallas)
        return y.astype(x.dtype)

    if variant in ("windowed", "fft"):
        # large-rank fused pipeline (PR 3): same two-pass structure, Gram
        # in coefficient form — streamed band blocks (windowed) or a
        # circulant rfft/irfft between the passes (fft); one differentiable
        # op either way, so large-rank training stays on the kernel path
        y = ops.ski_fused_tno_coef(x, plan["a_coef"], params["filt"],
                                   idx_lo, w_lo, r, causal, variant,
                                   use_pallas=cfg.use_pallas)
        return y.astype(x.dtype)

    # unfused 4-kernel fallback (fused disabled): FFT Gram matvec
    # (each Pallas op here carries its own custom VJP, so this path is
    # trainable too)
    z = ops.interp_reduce(x, idx_lo, w_lo, r, use_pallas=cfg.use_pallas)
    y_sparse = ops.short_conv(x, params["filt"], causal,
                              use_pallas=cfg.use_pallas)
    zt = jnp.swapaxes(z, 1, 2)                                 # (b, d, r)
    zt = toeplitz.toeplitz_matvec(plan["a_coef"][None], zt)    # A z
    z2 = jnp.swapaxes(zt, 1, 2)                                # (b, r, d)
    y_low = ops.interp_expand(z2, idx_lo, w_lo, use_pallas=cfg.use_pallas)
    return (y_sparse + y_low).astype(x.dtype)


def ski_dense_oracle(params, cfg: SKIConfig, n: int) -> jax.Array:
    """Materialise T_sparse + W A W^T as dense (d, n, n) — tests only."""
    from repro.kernels.ref import dense_interp_matrix
    r = min(cfg.rank, n)
    idx_lo, w_lo, h = make_inducing(n, r)
    w = dense_interp_matrix(idx_lo, w_lo, r)                   # (n, r)
    a_coef = inducing_gram_coeffs(params, cfg, r, h)
    a = toeplitz.dense_toeplitz(a_coef, r)                     # (d, r, r)
    t_low = jnp.einsum("nr,drs,ms->dnm", w, a, w)
    # sparse part as a banded matrix
    m = cfg.filter_size
    left = m // 2
    filt = params["filt"]
    i = jnp.arange(n)
    lag = i[:, None] - i[None, :]
    k_idx = lag + left                                         # tap index
    valid = (k_idx >= 0) & (k_idx < m)
    t_sp = jnp.where(valid[None], filt[:, jnp.clip(k_idx, 0, m - 1)], 0.0)
    return t_low + t_sp
