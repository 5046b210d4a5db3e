"""A training run of the harness at tiny widths on the CPU, with the timed
path broken underneath, comes out not correct against the cell's
committed limits; the same run unbroken comes out correct."""
import jax
import pytest

from bench import check, common
from bench.drivers import train


@pytest.fixture
def rehearsal(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path))


def run_cell(name, **kw):
    c = common.cell(name)
    out = train.run(c, seed=2**32 + 5, seconds=1.0, trace=False,
                    rehearse=True, t_start=0.0, devs=jax.devices()[:1], **kw)
    return out, check.judge(out["numbers"], common.cell_limits(c, True))


@pytest.mark.parametrize("cell", common.cell_names("train"))
def test_sound_run_is_correct(rehearsal, cell):
    out, (correct, rec) = run_cell(cell)
    assert correct, rec
    assert out["window"]["steps"] >= 1 and out["e2e"]["setup_s"] > 0


@pytest.mark.parametrize("cell", common.cell_names("train"))
@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_broken_step_is_not_correct(rehearsal, cell, fault):
    out, (correct, rec) = run_cell(cell, fault=fault)
    assert not correct, rec
    if fault == "frozen":
        assert rec["update_gap"]["value"] == pytest.approx(1.0)
