"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler installed with JAX compiles for a
topology that is only described (``topologies.get_topology_desc``). A
compile that passes proves Mosaic accepts the kernel at the paper's
widths (b=8, n=512, d=512, r=64, m=32) — tile alignment, VMEM budget and
supported primitives — which interpret mode cannot show. Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fd_fused, interp_matvec, short_conv, ski_fused
from repro.kernels import ski_grad, ski_vjp

B, N, D, R, M = 8, 512, 512, 64, 32
NL, RL = 8192, 2048                  # large-rank SKI widths
F = N + 1                            # rfft bins of the length-2n embed


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _vjp_of(fn):
    """Compile target for the backward: the pullback of ``fn`` in every
    argument, with the cotangent an input (a constant cotangent would let
    the backward kernels run eagerly on the host while tracing)."""
    def pullback(args, ct):
        _, pull = jax.vjp(fn, *args)
        return pull(ct)
    return pullback


def _fd_tno(x, k):
    return fd_fused.fd_tno_pallas(x, k, False)


def _hilbert(kt):
    return fd_fused.hilbert_window_pallas(kt, N, interpret=False)


def _fd_mul(xr, xi, kr, ki):
    return fd_fused.fd_spectral_multiply_pallas(xr, xi, kr, ki,
                                                interpret=False)


def _fd_khat_grad(gr, gi, xr, xi):
    return fd_fused.fd_khat_grad_pallas(gr, gi, xr, xi, interpret=False)


def _short_conv(x, f):
    return short_conv.short_conv_pallas(x, f, True, interpret=False)


def _conv_tap_grad(g, x):
    return ski_grad.conv_tap_grad_pallas(g, x, M, 0, interpret=False)


def _interp_reduce(x):
    return interp_matvec.interp_reduce_pallas(x, None, None, R,
                                              interpret=False)


def _interp_expand(z):
    return interp_matvec.interp_expand_pallas(z, jnp.zeros((N,)), None,
                                              interpret=False)


def _ski_fused_tno(x, a, f):
    return ski_vjp.ski_fused_tno_pallas(x, a, f, R, False, False)


def _ski_pass2(x, z, a, f):
    return ski_fused.ski_fused_pass2_pallas(x, z, a, f, False,
                                            interpret=False)


def _gram_grad(gz, z):
    return ski_grad.gram_grad_pallas(gz, z, interpret=False)


def _ski_windowed(x, c, f):
    return ski_vjp.ski_fused_tno_coef_pallas(x, c, f, RL, False, "windowed",
                                             False)


def _ski_expand2(x, c, f):
    return ski_vjp.ski_fused_tno_coef_pallas(x, c, f, RL, False, "fft",
                                             False)


_X = (B, N, D)
_XL = (B, NL, D)
_SPEC = (B, F, D)
# name -> (function, argument shapes, also compile the grad)
CASES = {
    "fd_tno": (_fd_tno, [_X, (D, F)], True),
    "hilbert_window": (_hilbert, [(D, 2 * N)], True),
    "fd_mul": (_fd_mul, [_SPEC, _SPEC, (F, D), (F, D)], False),
    "fd_khat_grad": (_fd_khat_grad, [_SPEC] * 4, False),
    "short_conv": (_short_conv, [_X, (D, M)], True),
    "conv_tap_grad": (_conv_tap_grad, [_X, _X], False),
    "interp_reduce": (_interp_reduce, [_X], True),
    "interp_expand": (_interp_expand, [(B, R, D)], True),
    "ski_fused_tno": (_ski_fused_tno, [_X, (D, R, R), (D, M)], True),
    "ski_fused_pass2": (_ski_pass2, [_X, (B, R, D), (D, R, R), (D, M)],
                        False),
    "gram_grad": (_gram_grad, [(B, R, D), (B, R, D)], False),
    "ski_windowed": (_ski_windowed, [_XL, (D, 2 * RL - 1), (D, M)], True),
    "ski_expand2": (_ski_expand2, [_XL, (D, 2 * RL - 1), (D, M)], True),
}

PARAMS = [pytest.param(name, mode, id=f"{name}-{mode}")
          for name, (_, _, grad) in CASES.items()
          for mode in (("fwd", "grad") if grad else ("fwd",))]


def _compiled_text(one_chip, fn, shapes, mode):
    specs = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
             for s in shapes]
    if mode == "fwd":
        lowered = jax.jit(fn).lower(*specs)
    else:
        ct = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            jax.eval_shape(fn, *specs))
        lowered = jax.jit(_vjp_of(fn)).lower(specs, ct)
    return lowered.compile().as_text()


@pytest.mark.parametrize("name,mode", PARAMS)
def test_kernel_compiles_for_v5e(one_chip, name, mode):
    fn, shapes, _ = CASES[name]
    assert "tpu_custom_call" in _compiled_text(one_chip, fn, shapes, mode)


@pytest.mark.parametrize("mode", ["fwd", "grad"])
def test_fd_tno_mxu_kernels_compile_at_train_fd_8k(one_chip, mode):
    """The FD-TNO op at train_fd_8k's (3, 8192, 512): the signal-side MXU
    kernel of each direction fits VMEM at the real width, and no array of
    the (b, ·, d) signal but x, y and their cotangents is left in the
    program — no padded copy, (b, n+1, d) spectrum or relayout, and no
    complex re/im join (``X64Combine``). The kernels read and write the
    signal as (b, n1/2, n2, d) = (3, 64, 128, 512), the same bytes."""
    b, n, d = 3, 8192, 512
    text = _compiled_text(one_chip, _fd_tno, [(b, n, d), (d, n + 1)], mode)
    assert ("fd_mxu_fwd" if mode == "fwd" else "fd_mxu_bwd") in text
    assert "X64Combine" not in text
    assert set(re.findall(r"\w+\[3,[0-9,]+\]", text)) <= {
        "f32[3,8192,512]", "f32[3,64,128,512]"}
