"""Operation and byte counts (bench/flops.py) pinned against values
worked by hand for the fd-tnn-lm-wt103 configuration's shapes, and the
peak table."""
import json
import os

import pytest

from bench import flops, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "bench", "configs",
                           "fd-tnn-lm-wt103.json")) as f:
        return json.load(f)


def test_matmul_params(cfg):
    # per layer GTU u/v/o 3 * 512^2 = 786,432, FFN 3 * 512 * 1024 =
    # 1,572,864: 2,359,296; six layers 14,155,776; head 512 * 50,265 =
    # 25,735,680 (real vocabulary, no padding, no embedding gather)
    assert flops.matmul_params(cfg) == 39_891_456


def test_fft_convention():
    # 2.5 N log2 N: N = 1024 -> 2.5 * 1024 * 10
    assert flops.fft_flops(1024) == 25_600.0


def test_train_flops_per_token_at_512(cfg):
    # matmuls 6 * 39,891,456 = 239,348,736; mixer forward per token
    # 512 * (2 * 2.5 * 1024 * 10 + 6 * 513) / 512 = 54,278, three passes
    # over six layers = 977,004
    assert flops.train_flops_per_token(cfg, 512) == pytest.approx(
        240_325_740.0)


def test_fd_tno_op_cost_b3_n8192(cfg):
    # sig = 3 * 512 signals, fft(16384) = 2.5 * 16384 * 14 = 573,440
    # 4 * 1536 * 573,440       = 3,523,215,360  signal transforms
    # 12 * 1536 * 8193         =   151,013,376  two complex multiplies
    # 8 * 1536 * 8193          =   100,675,584  kernel cotangent
    # 4 * 512 * 573,440        = 1,174,405,120  spectrum both ways
    # 2 * 512 * 2 * 8192       =    16,777,216  lag windows
    ops, nbytes = flops.fd_tno_op_cost(cfg, 3, 8192)
    assert ops == 4_966_086_656.0
    # forward: x and y, 2 * 3 * 8192 * 512 * 4 B = 100,663,296, and the
    # response, 512 * 8193 * 4 B = 16,779,264; backward: g, x and dx,
    # 150,994,944, the response and its cotangent, 33,558,528
    assert nbytes == 100_663_296 + 16_779_264 + 150_994_944 + 33_558_528
    assert nbytes == 301_996_032.0


def test_peaks_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
