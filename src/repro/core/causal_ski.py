"""Appendix-B negative result: causal masking negates SKI's benefits.

The causally-masked low-rank action x'_i = [W A]_i^T s_i with the
cumulative sums s_i = Σ_{j≤i} w_j x_j requires O(n r) work *and* an
(b, n, r, d) intermediate. On TPU the serial cumsum maps to
``associative_scan`` (log-depth) but the O(n r d) memory/work loss vs
O(n + r log r) stands — it is implemented to show the negative result,
exactly as the paper argues for GPUs. Its speed against FD is a chip
measurement that no benchmark cell makes yet.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import toeplitz
from repro.core.ski import SKIConfig, inducing_gram_coeffs, make_inducing
from repro.kernels.ref import dense_interp_matrix


def causal_ski_lowrank(params, cfg: SKIConfig, x: jax.Array) -> jax.Array:
    """Causally-masked W A W^T action via cumulative sums. x: (b, n, d)."""
    b, n, d = x.shape
    r = min(cfg.rank, n)
    idx_lo, w_lo, h = make_inducing(n, r)
    w = dense_interp_matrix(idx_lo, w_lo, r)                    # (n, r)
    a_coef = inducing_gram_coeffs(params, cfg, r, h)            # (d, 2r-1)
    a = toeplitz.dense_toeplitz(a_coef, r)                      # (d, r, r)

    # s_i = sum_{j<=i} w_j x_j  -> (b, n, r, d) intermediate (the blow-up)
    wx = w[None, :, :, None] * x[:, :, None, :].astype(jnp.float32)
    s = jnp.cumsum(wx, axis=1)
    # y_i = (A^T w_i)^T s_i  per channel
    wa = jnp.einsum("nr,drs->nds", w, a)                        # (n, d, r)
    y = jnp.einsum("nds,bnsd->bnd", wa, s)
    return y.astype(x.dtype)
