"""Substrate tests: optimizer, data pipeline determinism/elasticity,
checkpoint atomicity + elastic restore, trainer fault tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manifest as ckpt
from repro.data.pipeline import DataConfig, batch_at
from repro.optim import adamw
from repro.runtime.trainer import StragglerMonitor, Trainer, TrainerConfig


# ---------------------------------------------------------------- optim
def test_adamw_converges_quadratic():
    cfg = adamw.OptConfig(lr=0.1, warmup_steps=5, total_steps=200,
                          weight_decay=0.0, clip_norm=100.0)
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = adamw.init(cfg, params)
    for _ in range(200):
        grads = {"w": 2 * (params["w"] - target)}
        state, params, _ = adamw.step(cfg, state, grads, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_schedule_warmup_cosine():
    cfg = adamw.OptConfig(lr=1.0, warmup_steps=10, total_steps=110,
                          min_lr_frac=0.1)
    lr0 = float(adamw.schedule(cfg, jnp.int32(0)))
    lr_w = float(adamw.schedule(cfg, jnp.int32(10)))
    lr_end = float(adamw.schedule(cfg, jnp.int32(110)))
    assert lr0 < 0.05 and abs(lr_w - 1.0) < 1e-5
    assert abs(lr_end - 0.1) < 1e-3


def test_clip_by_global_norm():
    g = {"a": jnp.ones(4) * 3.0, "b": jnp.ones(9) * 4.0}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    total = np.sqrt(sum(float(jnp.sum(x ** 2))
                        for x in jax.tree.leaves(clipped)))
    assert abs(total - 1.0) < 1e-4
    assert float(norm) > 1.0


def test_int8_error_feedback_unbiased_over_time():
    """Error feedback: the *cumulative* compressed signal tracks the
    cumulative true signal (bias does not accumulate)."""
    rng = np.random.default_rng(0)
    err = jnp.zeros(64)
    true_sum = np.zeros(64)
    sent_sum = np.zeros(64)
    for _ in range(50):
        g = jnp.asarray(rng.normal(size=64).astype(np.float32))
        deq, err = adamw.compress_with_feedback(g, err)
        true_sum += np.asarray(g)
        sent_sum += np.asarray(deq)
    resid = np.abs(true_sum - sent_sum).max()
    scale = np.abs(true_sum).max()
    assert resid < 0.05 * scale, (resid, scale)


def test_bf16_moments_still_converge():
    cfg = adamw.OptConfig(lr=0.1, warmup_steps=1, total_steps=300,
                          weight_decay=0.0, moments_dtype="bfloat16")
    target = jnp.array([0.5, -1.5])
    params = {"w": jnp.zeros(2)}
    state = adamw.init(cfg, params)
    assert state.mu["w"].dtype == jnp.bfloat16
    for _ in range(300):
        grads = {"w": 2 * (params["w"] - target)}
        state, params, _ = adamw.step(cfg, state, grads, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=5e-2)


# ----------------------------------------------------------------- data
def test_data_deterministic_and_elastic():
    base = dict(vocab=64, seq_len=32, global_batch=8, seed=3)
    whole = batch_at(DataConfig(**base), step=7)
    again = batch_at(DataConfig(**base), step=7)
    np.testing.assert_array_equal(whole["tokens"], again["tokens"])

    # 2-host split reproduces the identical global batch (elastic invariant)
    h0 = batch_at(DataConfig(**base, host_id=0, num_hosts=2), step=7)
    h1 = batch_at(DataConfig(**base, host_id=1, num_hosts=2), step=7)
    glued = np.concatenate([h0["tokens"], h1["tokens"]], axis=0)
    np.testing.assert_array_equal(whole["tokens"], glued)


def test_data_steps_differ():
    cfg = DataConfig(vocab=64, seq_len=32, global_batch=4, seed=0)
    a = batch_at(cfg, 0)["tokens"]
    b = batch_at(cfg, 1)["tokens"]
    assert not np.array_equal(a, b)


def test_lra_match_task_is_learnable_signal():
    cfg = DataConfig(vocab=32, seq_len=64, global_batch=64, seed=0,
                     kind="lra_match")
    batch = batch_at(cfg, 0)
    toks, labels = batch["tokens"], batch["labels"]
    match = toks[:, 1] == toks[:, 62]
    np.testing.assert_array_equal(match.astype(np.int32), labels[:, 0])
    assert 0.2 < labels[:, 0].mean() < 0.8        # both classes present


def test_bytes_source(tmp_path):
    p = tmp_path / "corpus.bin"
    p.write_bytes(bytes(range(256)) * 64)
    cfg = DataConfig(vocab=256, seq_len=16, global_batch=2, seed=0,
                     kind="bytes", path=str(p))
    b = batch_at(cfg, 0)
    assert b["tokens"].shape == (2, 16)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


# ----------------------------------------------------------- checkpoint
def _tree():
    return {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones(4, jnp.bfloat16)},
            "step": jnp.int32(7)}


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    os.makedirs(d)
    ckpt.save(d, 3, _tree(), extra={"data_step": 3})
    out, extra = ckpt.restore(d, jax.tree.map(jnp.zeros_like, _tree()))
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(_tree()["w"]))
    assert out["nested"]["b"].dtype == jnp.bfloat16
    assert extra["data_step"] == 3


def test_checkpoint_atomicity_crash_window(tmp_path):
    """A half-written step dir without COMMITTED must be ignored."""
    d = str(tmp_path / "ck")
    os.makedirs(d)
    ckpt.save(d, 1, _tree())
    # simulate crash: step dir exists, no COMMITTED marker, stale LATEST
    os.makedirs(os.path.join(d, "step_000000002/data"))
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("2")
    assert ckpt.latest_step(d) == 1
    out, _ = ckpt.restore(d, jax.tree.map(jnp.zeros_like, _tree()))
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(_tree()["w"]))


def test_async_checkpointer_and_gc(tmp_path):
    d = str(tmp_path / "ck")
    acp = ckpt.AsyncCheckpointer(d, keep=2)
    for s in (1, 2, 3, 4):
        acp.save_async(s, _tree(), extra={"data_step": s})
    acp.wait()
    assert ckpt.latest_step(d) == 4
    committed = [p for p in os.listdir(d) if p.endswith(".COMMITTED")]
    assert len(committed) == 2                    # gc kept last 2


def test_elastic_restore_reshards(tmp_path):
    """Save from a 1-device layout, restore with explicit NamedShardings
    on a different (1x1) mesh — the elastic path."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    d = str(tmp_path / "ck")
    os.makedirs(d)
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    ckpt.save(d, 1, tree)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    sh = {"w": NamedSharding(mesh, P(None))}
    out, _ = ckpt.restore(d, tree, shardings=sh)
    assert out["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(8))


# -------------------------------------------------------------- trainer
def _toy_train_setup(tmp_path, total_steps=8, fail_at=None, **tkw):
    calls = {"n": 0}

    def train_step(state, batch):
        loss = jnp.float32(1.0 / (1 + state["step"]))
        return ({"step": state["step"] + 1},
                {"loss": loss, "tok0": jnp.float32(batch["tokens"][0, 0])})

    def failure_hook(step, attempt):
        calls["n"] += 1
        if fail_at is not None and step == fail_at and attempt == 0:
            raise RuntimeError("injected fault")

    dcfg = DataConfig(vocab=16, seq_len=8, global_batch=2, seed=0)
    tcfg = TrainerConfig(total_steps=total_steps,
                         ckpt_dir=str(tmp_path / "ck"),
                         ckpt_every=4, log_every=0, **tkw)
    return Trainer(tcfg, train_step, dcfg, failure_hook=failure_hook), calls


def test_trainer_runs_and_checkpoints(tmp_path):
    trainer, _ = _toy_train_setup(tmp_path)
    state, end = trainer.run({"step": jnp.int32(0)})
    assert end == 8 and int(state["step"]) == 8
    assert ckpt.latest_step(str(tmp_path / "ck")) == 8


def test_trainer_step_retry_on_injected_fault(tmp_path):
    trainer, calls = _toy_train_setup(tmp_path, fail_at=3)
    state, end = trainer.run({"step": jnp.int32(0)})
    assert end == 8                                # survived the fault
    assert calls["n"] == 9                         # one retry


def test_trainer_fails_after_max_retries(tmp_path):
    def always_fail(step, attempt):
        raise RuntimeError("dead node")
    dcfg = DataConfig(vocab=16, seq_len=8, global_batch=2, seed=0)
    tcfg = TrainerConfig(total_steps=4, max_retries=1, log_every=0)
    tr = Trainer(tcfg, lambda s, b: (s, {"loss": jnp.float32(1)}), dcfg,
                 failure_hook=always_fail)
    with pytest.raises(RuntimeError, match="failed after"):
        tr.run({"step": jnp.int32(0)})


def test_trainer_restart_resumes_from_checkpoint(tmp_path):
    trainer, _ = _toy_train_setup(tmp_path, total_steps=4)
    state, end = trainer.run({"step": jnp.int32(0)})
    assert end == 4
    # a "new job" restores and continues to 8
    trainer2, _ = _toy_train_setup(tmp_path, total_steps=8)
    state0 = {"step": jnp.int32(0)}
    state, start = trainer2.try_restore(state0)
    assert start == 4 and int(state["step"]) == 4
    state, end = trainer2.run(state, start)
    assert end == 8 and int(state["step"]) == 8


def test_trainer_nan_guard_retries_then_raises(tmp_path):
    def nan_step(state, batch):
        return state, {"loss": jnp.float32(np.nan)}
    dcfg = DataConfig(vocab=16, seq_len=8, global_batch=2, seed=0)
    tcfg = TrainerConfig(total_steps=2, max_retries=1, log_every=0)
    tr = Trainer(tcfg, nan_step, dcfg)
    with pytest.raises(RuntimeError, match="failed after"):
        tr.run({"s": jnp.int32(0)})


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(factor=3.0, alpha=0.5)
    for i in range(5):
        assert not mon.observe(i, 1.0)
    assert mon.observe(5, 10.0)                    # 10x the EMA
    assert mon.flagged and mon.flagged[0][0] == 5
    assert not mon.observe(6, 1.0)                 # EMA not poisoned


# ---------------------------------------------- restore validation (PR 6)
def test_manifest_restore_leaf_count_mismatch_raises(tmp_path):
    """Real exceptions, not asserts: a mismatched tree must fail loudly
    even under `python -O`."""
    d = str(tmp_path / "ck")
    os.makedirs(d)
    ckpt.save(d, 1, {"a": jnp.zeros(3)})
    with pytest.raises(ValueError, match="leaves"):
        ckpt.restore(d, {"a": jnp.zeros(3), "b": jnp.zeros(2)})


def test_manifest_restore_shape_mismatch_raises(tmp_path):
    d = str(tmp_path / "ck")
    os.makedirs(d)
    ckpt.save(d, 1, {"a": jnp.zeros((3,))})
    with pytest.raises(ValueError, match="shape mismatch"):
        ckpt.restore(d, {"a": jnp.zeros((4,))})


# ------------------------------------------ donated-buffer retry (PR 6)
def test_trainer_retry_survives_donated_buffer_invalidation(tmp_path):
    """train_step is jit'd with donated state: a step that fails *after*
    consuming its buffers leaves them invalidated, so a naive retry
    replays on dead arrays. The trainer must rebuild from the undonated
    host-side copy taken before the attempt."""
    calls = {"n": 0}

    def donating_step(state, batch):
        calls["n"] += 1
        if calls["n"] == 1:
            for leaf in jax.tree.leaves(state):
                leaf.delete()   # what a donated, failed jit call leaves
            raise RuntimeError("step failed after consuming donated buffers")
        return ({"w": state["w"] + 1}, {"loss": jnp.float32(1.0)})

    dcfg = DataConfig(vocab=16, seq_len=8, global_batch=2, seed=0)
    tcfg = TrainerConfig(total_steps=2, max_retries=2, log_every=0)
    tr = Trainer(tcfg, donating_step, dcfg)
    state, end = tr.run({"w": jnp.arange(4, dtype=jnp.float32)})
    assert end == 2 and calls["n"] == 3            # one retry, then clean
    np.testing.assert_array_equal(np.asarray(state["w"]), np.arange(4) + 2)


def test_trainer_retry_unsafe_without_undonated_copy(tmp_path):
    """The hazard the copy exists for: with undonated_retry_copy=False
    the retry replays on deleted buffers and every attempt fails."""
    def donating_step(state, batch):
        for leaf in jax.tree.leaves(state):
            if not leaf.is_deleted():
                leaf.delete()
                raise RuntimeError("consumed donated buffers")
        return ({"w": state["w"] + 1}, {"loss": jnp.float32(1.0)})

    dcfg = DataConfig(vocab=16, seq_len=8, global_batch=2, seed=0)
    tcfg = TrainerConfig(total_steps=2, max_retries=2, log_every=0,
                         undonated_retry_copy=False)
    tr = Trainer(tcfg, donating_step, dcfg)
    with pytest.raises(RuntimeError, match="failed after"):
        tr.run({"w": jnp.arange(4, dtype=jnp.float32)})


# ------------------------------------------- host copy only while donating
def _w_step(state, batch):
    tok = jnp.mean(batch["tokens"].astype(jnp.float32))
    return ({"w": state["w"] * 0.5 + tok},
            {"loss": jnp.sum(jnp.abs(state["w"]))})


def _retry_run(jitted, fail_at=None):
    """Six steps of ``jitted`` from a state on a 1-device mesh sharding;
    with ``fail_at``, attempt 0 of that step raises after the call
    returned. Returns (final w, registry, the ``w`` each attempt at
    ``fail_at`` was given)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.obs.metrics import Registry
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    done, given = [], []

    def train_step(state, batch):
        if len(done) == fail_at:
            given.append(state["w"])
        out = jitted(state, batch)
        if len(given) == 1:
            raise RuntimeError("injected fault after the step call")
        done.append(len(done))
        return out

    reg = Registry()
    dcfg = DataConfig(vocab=16, seq_len=8, global_batch=2, seed=0)
    tr = Trainer(TrainerConfig(total_steps=6, log_every=0),
                 train_step, dcfg, metrics=reg)
    w0 = jax.device_put(jnp.arange(4, dtype=jnp.float32),
                        NamedSharding(mesh, P()))
    out, end = tr.run({"w": w0})
    assert end == 6
    return np.asarray(out["w"]), reg, given


def test_trainer_undonated_step_copies_state_once_and_retries_live(
        tmp_path):
    """A step that does not donate leaves its input alive, so after the
    first step the Trainer takes no host copy, and a retry after a
    failure inside the step call starts from the live state and ends
    where a fault-free run ends."""
    step = jax.jit(_w_step)
    clean, _, _ = _retry_run(step)
    w, reg, given = _retry_run(step, fail_at=3)
    np.testing.assert_array_equal(w, clean)
    assert reg.get("repro_train_state_copies_total").get() == 1
    assert reg.get("repro_train_retries_total").get() == 1
    assert len(given) == 2 and given[1] is given[0]


def test_trainer_donating_jit_copies_every_step_and_rebuilds_sharded(
        tmp_path):
    """A real donating jit deletes its input on every call, so the
    Trainer copies the state before every step; a failure after the
    donated call at step 3 retries from that copy, put back with the
    input's sharding, and the run ends exactly where a fault-free one
    ends."""
    from jax.sharding import NamedSharding
    step = jax.jit(_w_step, donate_argnums=0)
    clean, _, _ = _retry_run(step)
    w, reg, given = _retry_run(step, fail_at=3)
    np.testing.assert_array_equal(w, clean)
    assert reg.get("repro_train_state_copies_total").get() == 6
    assert reg.get("repro_train_retries_total").get() == 1
    assert len(given) == 2 and given[1] is not given[0]
    assert given[1].sharding == given[0].sharding
    assert isinstance(given[1].sharding, NamedSharding)


def test_trainer_refuses_retry_on_state_deleted_without_copy(tmp_path):
    """A step that starts to delete its input only after the Trainer has
    seen it not donate has no host copy behind it: the Trainer raises at
    once and never replays on the dead arrays."""
    from repro.obs.metrics import Registry
    calls = []

    def train_step(state, batch):
        calls.append(len(calls))
        if len(calls) == 4:
            for leaf in jax.tree.leaves(state):
                leaf.delete()
            raise RuntimeError("consumed its input")
        return ({"w": state["w"] + 1}, {"loss": jnp.float32(1.0)})

    reg = Registry()
    dcfg = DataConfig(vocab=16, seq_len=8, global_batch=2, seed=0)
    tr = Trainer(TrainerConfig(total_steps=6, max_retries=2, log_every=0),
                 train_step, dcfg, metrics=reg)
    with pytest.raises(RuntimeError, match="no host copy"):
        tr.run({"w": jnp.arange(4, dtype=jnp.float32)})
    assert len(calls) == 4                         # no replay of step 3
    assert reg.get("repro_train_state_copies_total").get() == 1
