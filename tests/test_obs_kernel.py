"""Kernel-tier observability (repro.obs, ISSUE 10).

Contracts under test:

* cost model — Cost arithmetic, platform peaks (+ env overrides),
  roofline seconds/achieved-fraction math, plan-keyed dispatch on REAL
  ``ski_plan``/``tno_plan`` dicts, and the
  ``jit(...).lower().compile().cost_analysis()`` cross-check that pins
  the analytic estimators to XLA's own numbers on concrete shapes;
* compile watchdog — fresh traces counted + timed, retrace warnings
  past the declared budget, engine executables pinned to the shape
  family (a second identical fleet compiles nothing new);
* attribution — Chrome-trace aggregation, engine drain attribution
  coverage, memory gauges over a live fd DecodeState;
* bench history — drift gate passes flat/improving synthetic histories
  and fails a 20% regression; platform filtering;
* obs_report — histogram quantile interpolation and the span-vs-
  histogram TTFT/TPOT disagreement flag;
* lifecycle — the default tracer's atexit flush and the
  ``REPRO_METRICS_FILE`` final dump survive an exit without close();
  the train entrypoint emits both artifacts.
"""
import json
import math
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import ski
from repro.core.tno import TNOConfig, tno_init, tno_plan
from repro.models.transformer import init_model
from repro.nn.params import unbox
from repro.obs import compilewatch as obs_compile
from repro.obs import cost as obs_cost
from repro.obs import devstats as obs_devstats
from repro.obs.metrics import Registry
from repro.serving_engine import Engine, Request, Scheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import bench_history  # noqa: E402  (tools/ is not a package)
import obs_report  # noqa: E402

PLENS = [3, 6, 5, 2]
GENS = [6, 7, 8, 6]
MAX_LEN = 32


@pytest.fixture(scope="module")
def env():
    cfg = reduce_for_smoke(get_config("fd-tnn-lm-wt103"),
                           dtype="float32", param_dtype="float32")
    params, _ = unbox(init_model(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, (p,)).astype(np.int32)
               for p in PLENS]
    return {"cfg": cfg, "params": params, "prompts": prompts}


def _fleet(prompts, uid_prefix="r", gens=GENS, **kw):
    return [Request(uid=f"{uid_prefix}{i}", prompt=pr, max_new=g, **kw)
            for i, (pr, g) in enumerate(zip(prompts, gens))]


# ============================================================ cost model
def test_cost_arithmetic():
    a = obs_cost.Cost(10.0, 4.0)
    b = obs_cost.Cost(5.0, 1.0)
    assert (a + b).flops == 15.0 and (a + b).bytes == 5.0
    assert a.scale(3).flops == 30.0 and a.scale(3).bytes == 12.0
    t = obs_cost.total({"x": a, "y": b})
    assert t.flops == 15.0 and t.bytes == 5.0


def test_peaks_platforms_and_env_override(monkeypatch):
    v5e = obs_cost.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.mem_bw) == (197e12, 819e9)
    assert v5e.collective_bw > 0
    with pytest.raises(KeyError, match="TPU v4"):
        obs_cost.peaks("TPU v4")              # unknown kind: no default
    monkeypatch.setenv("REPRO_CPU_PEAK_FLOPS", "1e11")
    monkeypatch.setenv("REPRO_CPU_PEAK_BW", "4e10")
    pk = obs_cost.peaks("cpu")
    assert pk.flops == 1e11 and pk.mem_bw == 4e10
    monkeypatch.setenv("REPRO_CPU_PEAK_FLOPS", "fast")
    with pytest.raises(ValueError, match="REPRO_CPU_PEAK_FLOPS"):
        obs_cost.peaks("cpu")


def test_roofline_seconds_and_fraction():
    pk = obs_cost.Peaks(flops=100.0, mem_bw=10.0)
    compute_bound = obs_cost.Cost(flops=1000.0, bytes=1.0)
    s = obs_cost.seconds(compute_bound, pk)
    assert s["dominant"] == "compute" and s["bound_s"] == 10.0
    memory_bound = obs_cost.Cost(flops=1.0, bytes=1000.0)
    s = obs_cost.seconds(memory_bound, pk)
    assert s["dominant"] == "memory" and s["bound_s"] == 100.0
    # measured exactly at the roof -> 1.0; 10x slower -> 0.1
    assert obs_cost.achieved_fraction(compute_bound, 10.0, pk) \
        == pytest.approx(1.0)
    assert obs_cost.achieved_fraction(compute_bound, 100.0, pk) \
        == pytest.approx(0.1)
    assert math.isnan(obs_cost.achieved_fraction(compute_bound, 0.0, pk))


def test_ski_plan_cost_dispatch():
    """cost_of_plan keys off REAL ski_plan dicts and its kernel names
    track the plan's Gram variant."""
    cfg = ski.SKIConfig(d=8, rank=16, filter_size=4)
    params, _ = unbox(ski.ski_init(jax.random.PRNGKey(0), cfg))
    n = 64
    plan = ski.ski_plan(params, cfg, n)
    assert plan["variant"] == "dense"
    costs = obs_cost.cost_of_plan(plan, n=n, d=cfg.d, batch=2)
    assert set(costs) == {"interp_reduce", "ski_fused"}
    assert all(c.flops > 0 and c.bytes > 0 for c in costs.values())
    for variant, gram_key in (("windowed", "ski_windowed"),
                              ("fft", "ski_fft_gram")):
        p = ski.ski_plan(params, cfg, n, variant=variant)
        costs = obs_cost.cost_of_plan(p, n=n, d=cfg.d)
        assert set(costs) == {"interp_reduce", gram_key, "ski_expand2"}
    # the dense Gram costs more flops than the banded one at equal rank
    dense = obs_cost.gram_cost("dense", 64, 8)
    banded = obs_cost.gram_cost("windowed", 64, 8, bw=8)
    assert dense.flops > banded.flops
    with pytest.raises(ValueError, match="unknown gram variant"):
        obs_cost.gram_cost("sparse", 16, 8)


def test_fd_and_baseline_plan_cost():
    n = 24
    causal = TNOConfig(d=6, variant="fd", causal=True)
    p, _ = unbox(tno_init(jax.random.PRNGKey(0), causal))
    plan = tno_plan(p, causal, n)
    costs = obs_cost.cost_of_plan(plan, n=n, d=6)
    assert "hilbert_window" in costs         # causal: analytic completion
    assert {"rfft", "fd_mul"} <= set(costs)
    acausal = TNOConfig(d=6, variant="fd", causal=False)
    p2, _ = unbox(tno_init(jax.random.PRNGKey(1), acausal))
    costs2 = obs_cost.cost_of_plan(tno_plan(p2, acausal, n), n=n, d=6)
    assert "hilbert_window" not in costs2
    base = TNOConfig(d=6, variant="tno")
    p3, _ = unbox(tno_init(jax.random.PRNGKey(2), base))
    costs3 = obs_cost.cost_of_plan(tno_plan(p3, base, n), n=n, d=6)
    assert set(costs3) == {"toeplitz_fft"}
    with pytest.raises(ValueError, match="unrecognised plan keys"):
        obs_cost.cost_of_plan({"mystery": 1}, n=n, d=6)


def test_decode_step_cost_families(env):
    costs = obs_cost.decode_step_cost(env["cfg"], batch=4, max_len=MAX_LEN)
    # fd arch: every layer is a streaming fd mixer + projections + FFN
    assert {"embed", "fd_stream", "mixer_proj", "mlp", "lm_head"} \
        <= set(costs)
    assert "tno_hist" not in costs and "attention" not in costs
    assert obs_cost.total(costs).flops > 0
    # batch scales every per-token family linearly
    c1 = obs_cost.decode_step_cost(env["cfg"], batch=1, max_len=MAX_LEN)
    assert costs["mlp"].flops == pytest.approx(4 * c1["mlp"].flops)


# ------------------------------------------- XLA cost_analysis cross-check
def test_xla_cost_cross_check_matmul():
    """The estimator convention (2 flops per multiply-add) must agree
    with XLA's own cost_analysis on a plain matmul."""
    a = jnp.ones((32, 48), jnp.float32)
    b = jnp.ones((48, 16), jnp.float32)
    got = obs_cost.xla_cost(lambda x, y: x @ y, a, b)
    if got is None:
        pytest.skip("backend exposes no cost_analysis")
    analytic = 2.0 * 32 * 48 * 16
    assert analytic / 2 <= got["flops"] <= analytic * 2
    io_bytes = 4 * (32 * 48 + 48 * 16 + 32 * 16)
    assert got["bytes"] >= io_bytes / 4


def test_xla_cost_cross_check_short_conv():
    """short_conv_cost vs XLA on the repo's own depthwise conv op —
    within a small factor (XLA counts the padded/masked lanes too)."""
    from repro.kernels import ops
    b, n, m, d = 2, 64, 8, 8
    x = jnp.ones((b, n, d), jnp.float32)
    filt = jnp.ones((d, m), jnp.float32)
    got = obs_cost.xla_cost(
        lambda xx, ff: ops.short_conv(xx, ff, causal=True), x, filt)
    if got is None or got["flops"] <= 0:
        pytest.skip("backend exposes no cost_analysis for this op")
    est = obs_cost.short_conv_cost(n, m, d, b)
    ratio = est.flops / got["flops"]
    assert 0.1 <= ratio <= 10.0, (est.flops, got["flops"])


# ======================================================= compile watchdog
class _FakeLog:
    def __init__(self):
        self.warnings = []

    def warning(self, msg, *a):
        self.warnings.append(msg % a if a else msg)


def test_compilewatch_counts_time_and_warn():
    reg = Registry()
    log = _FakeLog()
    w = obs_compile.CompileWatch(metrics=reg, prefix="t.", logger=log)
    w.expect("f", 1)
    f = w.wrap("f", lambda x: x * 2)
    x4 = jnp.ones((4,))
    f(x4)
    f(x4)                                   # cached executable: no trace
    assert w.count("f") == 1 and not log.warnings
    f(jnp.ones((8,)))                       # new shape -> fresh trace
    assert w.count("f") == 2
    assert len(log.warnings) == 1
    assert "compile watchdog: t.f retraced" in log.warnings[0]
    c = reg.get("repro_compiles_total")
    assert c.get(fn="t.f") == 2
    h = reg.get("repro_compile_seconds").labels(fn="t.f")
    assert h.count == 2 and h.sum > 0       # both traces were timed


def test_compilewatch_untimed_mark():
    """A trace with no live call frame (AOT lower, warmup helpers) still
    counts, just without a latency observation."""
    reg = Registry()
    w = obs_compile.CompileWatch(metrics=reg)
    w._mark("g")
    assert w.count("g") == 1
    assert reg.get("repro_compiles_total").get(fn="g") == 1
    assert reg.get("repro_compile_seconds").labels(fn="g").count == 0


def test_engine_compiles_pinned_across_fleets(env):
    """Retrace pinning across the prefill bucket ladder: compiles track
    SHAPES, not request count — a second identical fleet through the
    same engine compiles nothing new."""
    eng = Engine(env["cfg"], env["params"], slots=4, max_len=MAX_LEN,
                 metrics=Registry())
    sched = Scheduler(eng)
    for r in _fleet(env["prompts"], "a"):
        sched.submit(r)
    results, state = sched.run()
    assert all(len(results[f"a{i}"]) == g for i, g in enumerate(GENS))
    first = eng.compile_watch.counts()
    assert first and first.get("generate", 0) >= 1
    sched2 = Scheduler(eng)
    for r in _fleet(env["prompts"], "b"):
        sched2.submit(r)
    results2, _ = sched2.run(state)
    assert all(len(results2[f"b{i}"]) == g for i, g in enumerate(GENS))
    assert eng.compile_watch.counts() == first
    # within the declared shape-family budgets: nothing warned
    for name, n in first.items():
        exp = eng.compile_watch._expected.get(name)
        assert exp is None or n <= exp, (name, n, exp)


# ============================================================ attribution
def test_aggregate_chrome_synthetic():
    P = obs_devstats.KERNEL_SCOPE_PREFIX
    events = [
        {"name": P + "fd_mul", "ph": "X", "dur": 1500.0},
        {"name": P + "fd_mul", "ph": "X", "dur": 500.0},
        {"name": P + "rfft", "ph": "B", "ts": 100.0, "pid": 1, "tid": 2},
        {"name": P + "rfft", "ph": "E", "ts": 400.0, "pid": 1, "tid": 2},
        {"name": "unrelated", "ph": "X", "dur": 9e9},
    ]
    got = obs_devstats.aggregate_chrome(events)
    assert got == {"fd_mul": pytest.approx(2e-3),
                   "rfft": pytest.approx(3e-4)}


def test_attribute_engine_coverage_and_memory(env):
    """The CPU-honest attribution path: engine-drain seconds split by
    analytic FLOP shares must account for most of the measured drain,
    and the memory gauges see the fd streaming cache."""
    reg = Registry()
    eng = Engine(env["cfg"], env["params"], slots=4, max_len=MAX_LEN,
                 metrics=reg)
    sched = Scheduler(eng, metrics=reg)
    for r in _fleet(env["prompts"]):
        sched.submit(r)
    t0 = time.perf_counter()
    _, state = sched.run()
    drain_s = time.perf_counter() - t0
    attr = obs_devstats.attribute_engine(eng, reg, drain_s=drain_s)
    assert attr["device_s"] > 0
    assert attr["coverage"] is not None and attr["coverage"] >= 0.5
    kernels = {row["kernel"] for row in attr["rows"]}
    assert "fd_stream" in kernels and "mlp" in kernels
    assert sum(row["frac"] for row in attr["rows"]) == pytest.approx(1.0)
    sec = reg.get("repro_kernel_seconds_total")
    assert sum(sec.get(kernel=k) for k in kernels) \
        == pytest.approx(attr["device_s"], rel=1e-6)
    fracs = reg.get("repro_kernel_roofline_frac")
    assert any(fracs.get(kernel=k) > 0 for k in kernels)

    mem = obs_devstats.sample_memory(reg, state)
    assert mem["repro_decode_cache_bytes"] > 0
    assert mem["repro_fd_stream_bytes"] > 0   # ring + spectra leaves
    assert mem["repro_fd_stream_bytes"] < mem["repro_decode_cache_bytes"]
    assert reg.get("repro_decode_cache_bytes").get() \
        == mem["repro_decode_cache_bytes"]
    # reuse dict: first call walks the pytree and fills the cache, later
    # calls republish the identical sizes without rewalking (the drain's
    # cache is fixed-shape — this keeps sampling off the hot path)
    reuse: dict = {}
    first = obs_devstats.sample_memory(reg, state, reuse=reuse)
    assert reuse["cache_bytes"] == first["repro_decode_cache_bytes"]
    reuse["cache_bytes"] += 1   # prove the cached value is what's used
    again = obs_devstats.sample_memory(reg, state, reuse=reuse)
    assert again["repro_decode_cache_bytes"] \
        == first["repro_decode_cache_bytes"] + 1


def test_mem_sample_every_env(monkeypatch):
    monkeypatch.delenv("REPRO_MEM_SAMPLE_EVERY", raising=False)
    assert obs_devstats.mem_sample_every() == 0
    monkeypatch.setenv("REPRO_MEM_SAMPLE_EVERY", "16")
    assert obs_devstats.mem_sample_every() == 16
    monkeypatch.setenv("REPRO_MEM_SAMPLE_EVERY", "often")
    with pytest.raises(ValueError, match="REPRO_MEM_SAMPLE_EVERY"):
        obs_devstats.mem_sample_every()


# =========================================================== bench history
def _engine_payload(tok_s=1000.0, speedup=15.0, prefill=2.0,
                    overhead=0.02, coverage=0.9, platform="cpu"):
    return {"bench": "engine", "platform": platform,
            "results": [{"slots": 16, "engine_tok_s": tok_s,
                         "speedup": speedup}],
            "prefill": {"speedup": prefill},
            "obs": {"overhead_frac": overhead,
                    "attributed_coverage": coverage}}


def _seed_history(tmp_path, payloads):
    for i, p in enumerate(payloads):
        bench_history.append_record(
            bench_history.make_record(p, sha=f"s{i}"), tmp_path)
    return bench_history.load_history("engine", tmp_path)


def test_drift_gate_flat_and_improving(tmp_path):
    hist = _seed_history(tmp_path, [_engine_payload()] * 3)
    flat = bench_history.make_record(_engine_payload(), sha="new")
    assert bench_history.check_drift(flat, hist) == []
    better = bench_history.make_record(
        _engine_payload(tok_s=1500.0, speedup=20.0, overhead=0.01,
                        coverage=0.95), sha="new")
    assert bench_history.check_drift(better, hist) == []


def test_drift_gate_fails_20pct_regression(tmp_path):
    hist = _seed_history(tmp_path, [_engine_payload()] * 3)
    worse = bench_history.make_record(
        _engine_payload(speedup=15.0 * 0.75), sha="bad")   # -25%
    failures = bench_history.check_drift(worse, hist)
    assert [f["metric"] for f in failures] == ["speedup_S16"]
    assert failures[0]["drift"] == pytest.approx(-0.25)
    # abs-slack metric: overhead rising past +0.05 fails too
    hot = bench_history.make_record(
        _engine_payload(overhead=0.09), sha="hot")
    failures = bench_history.check_drift(hot, hist)
    assert [f["metric"] for f in failures] == ["obs_overhead_frac"]


def test_drift_gate_platform_filter_and_empty(tmp_path):
    # only-TPU history never gates a CPU record (and vice versa)
    hist = _seed_history(
        tmp_path, [_engine_payload(speedup=100.0, platform="tpu")] * 3)
    cpu = bench_history.make_record(_engine_payload(speedup=1.0),
                                    sha="cpu")
    assert bench_history.check_drift(cpu, hist) == []
    assert bench_history.check_drift(cpu, []) == []   # first record wins


def test_bench_history_cli_roundtrip(tmp_path):
    payload = tmp_path / "BENCH_engine.json"
    payload.write_text(json.dumps(_engine_payload()))
    script = os.path.join(ROOT, "tools", "bench_history.py")
    hd = str(tmp_path / "hist")
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, script, "--history-dir", hd,
             "append", str(payload)],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
    ok = subprocess.run(
        [sys.executable, script, "--history-dir", hd,
         "check", str(payload)], capture_output=True, text=True)
    assert ok.returncode == 0 and "drift gate OK" in ok.stdout
    payload.write_text(json.dumps(_engine_payload(speedup=15.0 * 0.7)))
    bad = subprocess.run(
        [sys.executable, script, "--history-dir", hd,
         "check", str(payload)], capture_output=True, text=True)
    assert bad.returncode == 1 and "DRIFT: speedup_S16" in bad.stdout
    show = subprocess.run(
        [sys.executable, script, "--history-dir", hd, "show"],
        capture_output=True, text=True)
    assert show.returncode == 0 and "engine (2 records)" in show.stdout


def test_extract_metrics_tolerates_missing_obs():
    payload = _engine_payload()
    del payload["obs"]
    m = bench_history.extract_metrics(payload)
    assert "obs_overhead_frac" not in m and "speedup_S16" in m
    with pytest.raises(SystemExit, match="unknown bench"):
        bench_history.extract_metrics({"bench": "nope"})


# ============================================================= obs_report
def test_hist_quantile_interpolation():
    buckets, cum = [1.0, 2.0, 4.0], [2, 6, 8]
    v, lo, hi = obs_report.hist_quantile(buckets, cum, 8, 50)
    assert (lo, hi) == (1.0, 2.0)
    assert v == pytest.approx(1.5)          # target 4 is halfway into b2
    v, lo, hi = obs_report.hist_quantile(buckets, cum, 10, 99)
    assert v == 4.0 and hi == float("inf")  # overflow bucket
    v, _, _ = obs_report.hist_quantile(buckets, cum, 0, 50)
    assert math.isnan(v)


def test_compare_latency_agreement_flag():
    buckets, cum = [0.01, 0.1, 1.0], [0, 10, 10]
    hists = {"repro_ttft_seconds": [({}, buckets, cum, 2.0, 10)]}
    # spans inside the containing bucket: agree
    report = {"ttft": [0.05] * 10}
    rows = obs_report.compare_latency(report, hists)
    assert len(rows) == 2 and all(r["agree"] for r in rows)
    # spans far outside any bucket width: flagged
    rows = obs_report.compare_latency({"ttft": [40.0] * 10}, hists)
    assert rows and not any(r["agree"] for r in rows)


def test_load_histograms_prom_and_json_agree(tmp_path):
    reg = Registry()
    h = reg.histogram("repro_ttft_seconds", "ttft",
                      buckets=(0.01, 0.1, 1.0))
    for x in (0.05, 0.06, 0.5):
        h.observe(x)
    pj, pp = str(tmp_path / "m.json"), str(tmp_path / "m.prom")
    reg.dump_json(pj)
    reg.dump_prometheus(pp)
    hj = obs_report.load_histograms(pj)["repro_ttft_seconds"][0]
    hp = obs_report.load_histograms(pp)["repro_ttft_seconds"][0]
    assert hj[1] == hp[1] == [0.01, 0.1, 1.0]
    assert hj[2] == hp[2] == [0, 2, 3]
    assert hj[4] == hp[4] == 3


# ============================================================== lifecycle
def _run_py(body, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          capture_output=True, text=True, env=env)


def test_default_tracer_atexit_flush(tmp_path):
    """Fewer events than FLUSH_EVERY + exit without close(): the atexit
    hook must still land every event on disk (the satellite bugfix)."""
    path = str(tmp_path / "t.jsonl")
    r = _run_py("""
        from repro.obs import tracing
        t = tracing.default_tracer()
        assert t is not None and t.FLUSH_EVERY > 10
        for i in range(10):
            t.instant("tick", uid=str(i))
    """, {"REPRO_TRACE_FILE": path})
    assert r.returncode == 0, r.stderr
    lines = [ln for ln in open(path) if ln.strip()]
    assert len(lines) == 10
    assert json.loads(lines[-1])["uid"] == "9"


def test_metrics_file_env_final_dump(tmp_path):
    """REPRO_METRICS_FILE alone (no REPRO_METRICS) arms the default
    registry and dumps it at exit."""
    path = str(tmp_path / "m.prom")
    r = _run_py("""
        from repro.obs import metrics
        reg = metrics.default_registry()
        reg.counter("x_total", "x").inc(3)
    """, {"REPRO_METRICS_FILE": path})
    assert r.returncode == 0, r.stderr
    text = open(path).read()
    assert "x_total 3" in text


def test_train_entrypoint_emits_obs_artifacts(tmp_path):
    """--metrics-file/--trace-file parity with launch/serve.py."""
    mpath = str(tmp_path / "train.json")
    tpath = str(tmp_path / "train.jsonl")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "fd-tnn-lm-wt103", "--smoke", "--steps", "3",
         "--seq-len", "16", "--global-batch", "2",
         "--metrics-file", mpath, "--trace-file", tpath],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    doc = json.load(open(mpath))["metrics"]
    assert doc["repro_train_steps_total"]["series"][0]["value"] == 3
    compiles = doc["repro_compiles_total"]["series"]
    assert [(s["labels"]["fn"], s["value"]) for s in compiles] \
        == [("train.train_step", 1)]
    events = [json.loads(ln) for ln in open(tpath) if ln.strip()]
    steps = [e for e in events if e["name"] == "train_step"]
    assert len(steps) == 6                   # 3 steps x (B + E)
    assert {e["ph"] for e in steps} == {"B", "E"}
    assert os.path.exists(tpath + ".chrome.json")
