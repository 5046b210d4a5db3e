"""Operations and bytes the TNN LM's work requires, computed from the
configuration's shapes alone (never from the program).

Conventions:
* a multiply-add is 2 operations;
* a real FFT of length N is 2.5·N·log2(N) operations (split-radix real
  transform, the usual roofline convention), its inverse the same;
* a complex multiply is 6 operations, a complex multiply-add 8;
* "required" work leaves out recomputation and padding: the LM head is
  counted over the real vocabulary, not the padded one.
"""
from __future__ import annotations

import math


def fft_flops(n: int) -> float:
    return 2.5 * n * math.log2(max(n, 2))


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: GTU u/v/o and the FFN of each
    layer, and the LM head. The embedding is a gather, not a matmul."""
    d, f = cfg["d_model"], cfg["d_ff"]
    per_layer = 3 * d * d + 3 * d * f
    return cfg["n_layers"] * per_layer + d * cfg["vocab"]


def mixer_fwd_flops_per_token(cfg: dict, n: int) -> float:
    """FD and TNO mixers alike: rfft of the padded length-2n input and
    irfft of the product, per channel, plus the complex multiply over the
    n+1 bins; spread over the n tokens of a row. The kernel's own
    spectrum is computed once a row batch, not per token, and is left
    out here."""
    d = cfg["d_model"]
    return d * (2 * fft_flops(2 * n) + 6 * (n + 1)) / n


def train_flops_per_token(cfg: dict, n: int) -> float:
    """Forward and backward: 6 operations per matmul weight per token
    (2 forward, 4 backward), and three times the mixer's forward
    transforms (forward, the signal's cotangent, the kernel's)."""
    return (6.0 * matmul_params(cfg)
            + 3.0 * cfg["n_layers"] * mixer_fwd_flops_per_token(cfg, n))


def fd_tno_op_cost(cfg: dict, batch: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one call of the causal FD-TNO op, forward
    and backward, for a (batch, n, d) float32 input.

    Forward: rfft(pad x), complex multiply by the causal spectrum,
    irfft; the spectrum itself is irfft of the real response, the lag
    window, and rfft. Backward: rfft(pad g), multiply by the conjugate
    spectrum, irfft for dx; the kernel's cotangent sum_b g^ conj(x^)
    (x^ kept from the forward), then irfft, window, rfft back to the
    real response.

    Bytes are the least traffic of the two passes, each a program of its
    own that keeps nothing on chip from the other: the forward reads x
    and the (d, n+1) response and writes y; the backward reads g, reads
    x again (the op's residuals are its inputs, so x^ is made anew from
    x) and the response again, and writes dx and the response's
    cotangent. That is five (b, n, d) tensors and three (d, n+1)
    arrays."""
    d = cfg["d_model"]
    sig = batch * d
    f2n = fft_flops(2 * n)
    flops = (4 * sig * f2n             # rfft x, irfft y, rfft g, irfft dx
             + 2 * 6 * sig * (n + 1)   # forward and dx multiplies
             + 8 * sig * (n + 1)       # kernel cotangent multiply-add
             + 4 * d * f2n             # spectrum forward and backward
             + 2 * d * 2 * n)          # lag window, forward and backward
    elem = 4
    nbytes = elem * (5 * batch * n * d + 3 * d * (n + 1))
    return float(flops), float(nbytes)
