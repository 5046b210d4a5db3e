"""Continuous-batching serving engine (repro.serving_engine, ISSUE 5).

Contracts under test:
* ragged parity — S slots at staggered lengths emit token-for-token what
  independent solo ``generate`` calls emit (same length bucket), across
  {fd, tno, attention, mamba} × {fp32, bf16}, and at S = 16 for fd;
* jit stability — the generate/insert steps trace exactly once across
  steps, inserts, and evictions at fixed S;
* eviction/recycle — more requests than slots all complete through
  recycled slots;
* capacity — over-capacity prompts/requests raise instead of silently
  clamping cache writes (the ring-corruption fix);
* ragged fd_stream — the per-slot-position stream step is exactly the
  lockstep step applied row-wise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.kernels import fd_stream
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import generate
from repro.launch.steps import StepBuilder
from repro.models import serving
from repro.models.transformer import init_model
from repro.nn.params import unbox
from repro.serving_engine import Engine, Request, Scheduler

MIXER_ARCHS = {
    "tno": "tnn-lm-wt103",
    "fd": "fd-tnn-lm-wt103",
    "attention": "stablelm-3b",
    "mamba": "mamba2-2.7b",
}


def _setup(arch, dtype, seed=0):
    cfg = reduce_for_smoke(get_config(arch), dtype=dtype, param_dtype=dtype)
    params, _ = unbox(init_model(jax.random.PRNGKey(seed), cfg))
    return cfg, params


def _solo_tokens(cfg, params, prompts, gens, max_len):
    mesh = make_host_mesh()
    sb = StepBuilder(cfg, mesh)
    outs = []
    with mesh:
        for pr, g in zip(prompts, gens):
            toks = generate(sb, params, jnp.asarray(pr)[None], g,
                            max_len=max_len)
            outs.append(np.asarray(toks)[0, len(pr):])
    return outs


def _assert_engine_matches_solo(cfg, params, prompts, gens, *, max_len,
                                slots):
    """Drive every prompt through one engine of ``slots`` slots and
    assert each request's tokens equal its solo decode's; returns the
    engine."""
    solo = _solo_tokens(cfg, params, prompts, gens, max_len)
    eng = Engine(cfg, params, slots=slots, max_len=max_len)
    sched = Scheduler(eng)
    for i, (pr, g) in enumerate(zip(prompts, gens)):
        sched.submit(Request(uid=f"r{i}", prompt=pr, max_new=g))
    res, _ = sched.run()
    for i in range(len(prompts)):
        got = np.asarray(res[f"r{i}"])
        assert np.array_equal(got, solo[i]), (
            f"r{i}: engine {got} != solo {solo[i]}")
    return eng


# ------------------------------------------------------- ragged parity
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mixer", sorted(MIXER_ARCHS))
def test_engine_ragged_parity(mixer, dtype, monkeypatch):
    """4 staggered-length requests through S=4 slots == 4 independent
    solo decodes, token for token."""
    monkeypatch.setenv("REPRO_FD_STREAM_C", "4")
    cfg, params = _setup(MIXER_ARCHS[mixer], dtype)
    rng = np.random.default_rng(1)
    plens, gens = [3, 6, 5, 2], [8, 5, 6, 9]
    prompts = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32)
               for p in plens]
    _assert_engine_matches_solo(cfg, params, prompts, gens, max_len=24,
                                slots=4)


def test_engine_parity_and_one_trace_at_16_slots(monkeypatch):
    """16 requests through S=16 slots == 16 solo decodes, token for
    token, with staggered budgets, and the decode step traces once."""
    monkeypatch.setenv("REPRO_FD_STREAM_C", "4")
    cfg, params = _setup("fd-tnn-lm-wt103", "float32")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, (4,)).astype(np.int32)
               for _ in range(16)]
    gens = [6 - i % 4 for i in range(16)]
    eng = _assert_engine_matches_solo(cfg, params, prompts, gens,
                                      max_len=16, slots=16)
    assert eng.trace_counts["generate"] == 1, eng.trace_counts


def test_engine_eviction_recycle_more_requests_than_slots(monkeypatch):
    """6 requests over 2 slots: every slot is recycled, all requests
    complete, tokens stay exact, and streaming callbacks saw every
    token in order."""
    monkeypatch.setenv("REPRO_FD_STREAM_C", "4")
    cfg, params = _setup("fd-tnn-lm-wt103", "float32")
    rng = np.random.default_rng(2)
    plens = [3, 7, 5, 9, 4, 6]
    gens = [10, 6, 12, 8, 5, 7]
    prompts = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32)
               for p in plens]
    max_len = 32
    solo = _solo_tokens(cfg, params, prompts, gens, max_len)

    eng = Engine(cfg, params, slots=2, max_len=max_len)
    sched = Scheduler(eng)
    streamed = {}
    for i, (pr, g) in enumerate(zip(prompts, gens)):
        sched.submit(Request(
            uid=f"r{i}", prompt=pr, max_new=g,
            on_token=lambda uid, t: streamed.setdefault(uid, []).append(t)))
    res, _ = sched.run()
    assert sched.prefills == 6
    for i in range(6):
        assert np.array_equal(np.asarray(res[f"r{i}"]), solo[i]), i
        assert res[f"r{i}"] == streamed[f"r{i}"], i


def test_engine_eos_eviction(monkeypatch):
    """A request stops at its EOS token and frees the slot early; the
    queued request recycles it and still decodes exactly."""
    monkeypatch.setenv("REPRO_FD_STREAM_C", "4")
    cfg, params = _setup("fd-tnn-lm-wt103", "float32")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, (4,)).astype(np.int32)
               for _ in range(3)]
    max_len = 32
    solo = _solo_tokens(cfg, params, prompts, [12] * 3, max_len)
    # pick an EOS that actually occurs mid-stream for request 0
    eos = int(solo[0][3])
    want0 = list(solo[0][:list(solo[0]).index(eos) + 1])

    eng = Engine(cfg, params, slots=1, max_len=max_len)
    sched = Scheduler(eng)
    sched.submit(Request(uid="r0", prompt=prompts[0], max_new=12,
                         eos_id=eos))
    sched.submit(Request(uid="r1", prompt=prompts[1], max_new=12))
    res, _ = sched.run()
    assert res["r0"] == want0                     # truncated at EOS
    assert np.array_equal(np.asarray(res["r1"]), solo[1])


# --------------------------------------------------------- jit stability
def test_engine_jit_stable_across_steps_inserts_evictions(monkeypatch):
    """At fixed S the jitted functions trace exactly once each, across
    staggered inserts, boundary refreshes, evictions and recycles."""
    monkeypatch.setenv("REPRO_FD_STREAM_C", "4")
    cfg, params = _setup("fd-tnn-lm-wt103", "float32")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32)
               for p in [3, 7, 5, 9, 4]]
    eng = Engine(cfg, params, slots=2, max_len=32)
    sched = Scheduler(eng)
    for i, pr in enumerate(prompts):
        sched.submit(Request(uid=f"r{i}", prompt=pr, max_new=6 + i))
    sched.run()
    assert sched.prefills == 5 and sched.steps > 10
    assert eng.trace_counts["generate"] == 1, eng.trace_counts
    assert eng.trace_counts["insert"] <= 1, eng.trace_counts
    assert eng.trace_counts["decode1"] <= 1, eng.trace_counts
    assert eng.trace_counts["chunk1"] <= 1, eng.trace_counts
    # packed admission traces are bounded by SHAPES, never request count:
    # the first wave packs both free slots (one insert_from trace per
    # distinct packed batch size), recycled slots free up one at a time
    # (the sequential insert trace), and the bucketed prefill compiles at
    # most one executable per (batch, bucket, n_tok) triple
    assert sched.packed_prefills >= 1
    assert 1 <= eng.trace_counts["insert_from"] <= 2, eng.trace_counts
    assert eng.trace_counts["prefill_bucket"] <= 2 * len(eng.buckets), (
        eng.trace_counts)


def test_engine_slots_env(monkeypatch):
    from repro.serving_engine import default_slots
    monkeypatch.delenv("REPRO_ENGINE_SLOTS", raising=False)
    assert default_slots() == 8
    monkeypatch.setenv("REPRO_ENGINE_SLOTS", "3")
    assert default_slots() == 3
    monkeypatch.setenv("REPRO_ENGINE_SLOTS", "0")
    with pytest.raises(ValueError):
        default_slots()


def test_engine_rejects_zero_slots_and_duplicate_uid():
    """A 0-slot engine would make the scheduler spin forever; a reused
    uid would merge token lists and truncate the later request — both
    must raise at submission time."""
    cfg, params = _setup("fd-tnn-lm-wt103", "float32")
    with pytest.raises(ValueError, match="slots"):
        Engine(cfg, params, slots=0, max_len=16)
    eng = Engine(cfg, params, slots=1, max_len=16)
    sched = Scheduler(eng)
    pr = np.zeros((3,), np.int32)
    sched.submit(Request(uid="dup", prompt=pr, max_new=2))
    with pytest.raises(ValueError, match="already submitted"):
        sched.submit(Request(uid="dup", prompt=pr, max_new=2))


def test_insert_raises_on_unclassified_cache_leaf():
    """Every cache leaf must be declared per-slot or shared — a new leaf
    name must fail loud instead of silently leaking a recycled slot's
    previous state (treated-as-shared default)."""
    from repro.serving_engine.state import insert_prefix_cache
    dst = {"mystery": jnp.zeros((2, 4)), "hist": jnp.zeros((2, 4, 3))}
    src = {"mystery": jnp.ones((1, 4)), "hist": jnp.ones((1, 4, 3))}
    with pytest.raises(NotImplementedError, match="mystery"):
        insert_prefix_cache(dst, src, jnp.int32(0))


# ------------------------------------------------------------- capacity
def test_capacity_is_explicit_and_gates_admission():
    cfg, params = _setup("fd-tnn-lm-wt103", "float32")
    eng = Engine(cfg, params, slots=2, max_len=16)
    assert eng.capacity == 16
    rng = np.random.default_rng(5)
    with pytest.raises(ValueError, match="exceeds slot capacity"):
        eng.prefill(rng.integers(0, cfg.vocab, (17,)).astype(np.int32))
    sched = Scheduler(eng)
    with pytest.raises(ValueError, match="exceeds slot capacity"):
        sched.submit(Request(uid="big", max_new=10,
                             prompt=rng.integers(0, cfg.vocab, (8,))
                             .astype(np.int32)))
    # boundary case fits: 8 prompt + 9 generated = 16 written positions
    sched.submit(Request(uid="fit", max_new=9,
                         prompt=rng.integers(0, cfg.vocab, (8,))
                         .astype(np.int32)))
    res, _ = sched.run()
    assert len(res["fit"]) == 9


def test_cache_capacity_by_family():
    for arch, want in [("fd-tnn-lm-wt103", 24), ("tnn-lm-wt103", 24),
                       ("stablelm-3b", 24), ("mamba2-2.7b", None)]:
        cfg, params = _setup(arch, "float32")
        cache = serving.init_cache(cfg, 2, 24, params=params)
        assert serving.cache_capacity(cache) == want, arch
    assert fd_stream.stream_capacity(
        fd_stream.fd_stream_cache(jnp.ones((3, 24)), 1, 20, 8)) == 20


# ------------------------------------------------- ragged stream kernel
def test_stream_step_ragged_matches_lockstep_rows():
    """Vector-position stream_step == each row run alone with scalar
    positions, bit-for-bit, including parked rows pinned at position 0
    (the engine's inactive-slot convention) and staggered boundaries."""
    b, d, n, c = 3, 5, 16, 4
    k = jax.random.normal(jax.random.PRNGKey(0), (d, n))
    u = jax.random.normal(jax.random.PRNGKey(1), (b, n, d))
    starts = [0, 2, 7]                         # row i enters at step starts[i]

    # reference: each row alone, scalar positions
    refs = []
    for i in range(b):
        cache = fd_stream.fd_stream_cache(k, 1, n, c)
        ys = []
        for t in range(n - starts[i]):
            y, cache = fd_stream.stream_step(cache, u[i:i + 1, t],
                                             jnp.int32(t))
            ys.append(y[0])
        refs.append(np.asarray(jnp.stack(ys)))

    cache = fd_stream.fd_stream_cache(k, b, n, c)
    got = [[] for _ in range(b)]
    for step in range(n):
        # rows not yet started idle at position 0 with zero input
        pos = np.array([max(step - s, 0) for s in starts], np.int32)
        live = np.array([step >= s for s in starts])
        inp = np.stack([np.asarray(u[i, step - starts[i]]) if live[i]
                        else np.zeros((d,), np.float32) for i in range(b)])
        y, cache = fd_stream.stream_step(cache, jnp.asarray(inp),
                                         jnp.asarray(pos))
        for i in range(b):
            if live[i]:
                got[i].append(np.asarray(y[i]))
    for i in range(b):
        np.testing.assert_array_equal(np.stack(got[i]),
                                      refs[i][:len(got[i])], err_msg=f"row{i}")


def test_insert_leaves_other_slots_untouched(monkeypatch):
    """insert() is a pure slot-row slice-in: every per-slot leaf outside
    the target row is bitwise unchanged, shared leaves fully unchanged."""
    monkeypatch.setenv("REPRO_FD_STREAM_C", "4")
    cfg, params = _setup("fd-tnn-lm-wt103", "float32")
    eng = Engine(cfg, params, slots=3, max_len=16)
    state = eng.init_state()
    rng = np.random.default_rng(6)
    prefix, first, plen = eng.prefill(
        rng.integers(0, cfg.vocab, (5,)).astype(np.int32))
    # fill slot 0 then slot 2; slot 1 must stay zero
    state = eng.insert(state, prefix, plen, first, 0)
    before = jax.tree.map(lambda x: np.asarray(x), state.cache)
    state = eng.insert(state, prefix, plen, first, 2)
    after = jax.tree.map(lambda x: np.asarray(x), state.cache)

    from repro.serving_engine.state import BATCH_AXIS_FROM_END

    def check(path, a, b):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        leaf = names[-1] if names else ""
        off = BATCH_AXIS_FROM_END.get(leaf)
        if off is None:
            np.testing.assert_array_equal(a, b, err_msg=f"shared {leaf}")
            return a
        ax = a.ndim - off
        for s in (0, 1):                      # untouched slots
            np.testing.assert_array_equal(np.take(a, s, axis=ax),
                                          np.take(b, s, axis=ax),
                                          err_msg=f"{leaf} slot {s}")
        return a
    jax.tree_util.tree_map_with_path(check, before, after)
    assert bool(state.active[0]) and bool(state.active[2])
    assert not bool(state.active[1])
    assert int(state.cur_len[2]) == plen
