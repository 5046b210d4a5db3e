"""Distribution-layer tests on a small fake-device mesh (8 CPU devices via
subprocess-free reuse: these tests run in the main process only when the
device count allows; otherwise they validate the pure-python parts)."""
import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.launch.steps import SHAPES, StepBuilder, cell_is_applicable
from repro.parallel.sharding import ShardingRules, spec_for


def test_cell_applicability_matrix():
    """33 applicable cells: 10 archs × 4 shapes − 7 long_500k skips."""
    archs = ["jamba-1.5-large-398b", "grok-1-314b", "granite-moe-3b-a800m",
             "phi3-medium-14b", "qwen2-72b", "gemma3-4b", "stablelm-3b",
             "paligemma-3b", "whisper-medium", "mamba2-2.7b"]
    cells = [(a, s) for a in archs for s in SHAPES
             if cell_is_applicable(a, s)]
    assert len(cells) == 33
    assert ("qwen2-72b", "long_500k") not in cells
    assert ("mamba2-2.7b", "long_500k") in cells
    assert ("gemma3-4b", "long_500k") in cells
    assert ("jamba-1.5-large-398b", "long_500k") in cells


def test_shapes_match_assignment():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1


def test_spec_for_divisibility_guard():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(data_axes=("data",))
    # 'heads' -> model; extent 1 divides everything
    s = spec_for(mesh, rules, ("embed", "heads"), (64, 64))
    assert len(s) == 2


def test_spec_for_no_axis_reuse():
    """An axis already consumed by one dim must not shard a second dim."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(data_axes=("data",))
    s = spec_for(mesh, rules, ("heads", "ffn"), (16, 16))  # both -> model
    used = [x for x in s if x is not None]
    assert len(used) <= 1


def test_input_specs_shapes_per_kind():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("qwen2-72b")
    sb = StepBuilder(cfg, mesh)
    tr = sb.input_specs(SHAPES["train_4k"])
    assert tr["tokens"].shape == (256, 4096)
    assert tr["labels"].shape == (256, 4096)
    pf = sb.input_specs(SHAPES["prefill_32k"])
    assert pf["tokens"].shape == (32, 32768) and "labels" not in pf
    dc = sb.input_specs(SHAPES["decode_32k"])
    assert dc["batch"]["tokens"].shape == (128, 1)
    kv = jax.tree.leaves(dc["cache"])
    assert any(x.shape[-3] == 32768 for x in kv if hasattr(x, "shape"))


def test_abstract_params_match_param_count_scale():
    """eval_shape param total ≈ analytic param_count (no allocation)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch in ("qwen2-72b", "mamba2-2.7b"):
        cfg = get_config(arch)
        sb = StepBuilder(cfg, mesh)
        vals, axes = sb.abstract_params()
        total = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(vals))
        analytic = cfg.param_count()["total"]
        assert abs(total - analytic) / analytic < 0.05, (arch, total, analytic)


def test_qwen_total_params_near_72b():
    cfg = get_config("qwen2-72b")
    t = cfg.param_count()["total"]
    assert 6.5e10 < t < 8.5e10, t


def test_jamba_active_vs_total():
    cfg = get_config("jamba-1.5-large-398b")
    pc = cfg.param_count()
    assert 3.4e11 < pc["total"] < 4.6e11, pc     # ~398B class
    assert pc["active"] < 0.4 * pc["total"]      # 16e top-2 sparsity


def test_serve_ctx_folds_data_axes_for_batch1():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("mamba2-2.7b")
    sb = StepBuilder(cfg, mesh)
    ctx = sb.serve_ctx(SHAPES["long_500k"])
    # with 1-extent axes everything divides; logic check via big mesh is
    # covered by the dry-run. Here: decode ctx must disable seq-SP.
    assert ctx.decode and not ctx.seq_shard_resid


@pytest.mark.parametrize("use_pallas", [True, False])
def test_step_builder_use_pallas_reaches_tno(use_pallas):
    """StepBuilder(use_pallas=...) selects the SKI kernels (interpret mode
    here) or the jnp reference for the TNO mixers of the model it builds."""
    import jax.numpy as jnp
    from repro.configs import reduce_for_smoke
    from repro.kernels import ski_vjp
    cfg = reduce_for_smoke(get_config("ski-tnn-lm-wt103"), n_layers=1,
                           d_model=16, d_ff=32, vocab=64)
    sb = StepBuilder(cfg, use_pallas=use_pallas)
    state = sb.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    ski_vjp.reset_counters()
    _, metrics = sb.make_train_step()(state, {"tokens": tokens,
                                              "labels": tokens})
    assert np.isfinite(float(metrics["loss"]))
    assert ski_vjp.counters["fwd"] == (1 if use_pallas else 0)
    assert ski_vjp.counters["bwd_kernel"] == (1 if use_pallas else 0)


@pytest.mark.parametrize("broken", [False, True])
def test_serve_exit_code_reflects_outcomes(monkeypatch, capsys, broken):
    """launch/serve --engine exits non-zero when a request fails (here a
    prefill that raises, as a kernel that does not compile would)."""
    from repro.launch import compile_cache, serve
    from repro.serving_engine import Engine
    monkeypatch.setattr(compile_cache, "configure", lambda: None)
    if broken:
        def refuse(*args, **kwargs):
            raise RuntimeError("kernel refused")
        monkeypatch.setattr(Engine, "prefill", refuse)
        monkeypatch.setattr(Engine, "prefill_packed", refuse)
    rc = serve.main(["--arch", "fd-tnn-lm-wt103", "--smoke", "--engine",
                     "--slots", "2", "--batch", "2", "--prompt-len", "8",
                     "--gen-len", "4"])
    out = capsys.readouterr().out
    assert rc == (1 if broken else 0), out
    assert ("FAILED" in out) == broken
