"""Kernel-tier observability (repro.obs, ISSUE 10).

Contracts under test:

* compile watchdog — fresh traces counted + timed, retrace warnings
  past the declared budget, engine executables pinned to the shape
  family (a second identical fleet compiles nothing new);
* memory gauges over a live fd DecodeState;
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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.transformer import init_model
from repro.nn.params import unbox
from repro.obs import compilewatch as obs_compile
from repro.obs import devstats as obs_devstats
from repro.obs.metrics import Registry
from repro.serving_engine import Engine, Request, Scheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import obs_report  # noqa: E402  (tools/ is not a package)

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


# ========================================================== memory gauges
def test_sample_memory_sees_fd_stream_cache(env):
    """The memory gauges over a live fd DecodeState see the streaming
    cache (ring + spectra) as a part of the whole cache."""
    reg = Registry()
    eng = Engine(env["cfg"], env["params"], slots=4, max_len=MAX_LEN,
                 metrics=reg)
    sched = Scheduler(eng, metrics=reg)
    for r in _fleet(env["prompts"]):
        sched.submit(r)
    _, state = sched.run()

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
