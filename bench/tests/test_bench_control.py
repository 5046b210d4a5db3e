"""The control of each cell, at tiny widths on the CPU: the plain
reference computed in bfloat16 (the precision below the configurations'
float32) put in the program's place has to come out not correct against
the cell's committed limits."""
import jax
import jax.numpy as jnp
import pytest

from bench import check, common
from bench.drivers import train


@pytest.fixture
def rehearsal(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path))


@pytest.mark.parametrize("cell", common.cell_names("train"))
def test_bfloat16_reference_in_the_programs_place_fails(rehearsal, cell):
    c = common.cell(cell)
    out = train.run(c, seed=7, seconds=2.0, trace=False, rehearse=True,
                    t_start=0.0, devs=jax.devices()[:1],
                    control=jnp.bfloat16, check_only=True)
    limits = common.cell_limits(c, True)
    sound, rec = check.judge(out["numbers"], limits)
    control, crec = check.judge(out["control_numbers"], limits)
    assert sound, rec
    assert not control, crec
