"""Observability layer (repro.obs, ISSUE 9).

Contracts under test:

* registry semantics — counter monotonicity, label memoisation, kind
  collisions, cumulative histogram buckets, Prometheus text exposition
  well-formedness, JSON dump, the off-by-default NullRegistry, the
  REPRO_METRICS process default, MirroredCounts delta mirroring;
* span tracing — begin/end/instant ordering through a real scheduler
  run (packed admission + async detok), the validate_spans contract
  (positive and negative), Chrome trace_event export validity;
* chaos — injector firings land as tagged ``fault`` instants and
  labeled counters; faulted/quarantined/expired requests end with the
  matching terminal span status; preemption closes spans as
  ``preempted`` and a resumed run re-begins them;
* plumbing — engine trace_counts mirror into the registry, trainer
  step metrics, the REPRO_LOG_LEVEL logger knob, tools/obs_report.py;
* profiler spans — ``annotation`` and the trainer's spans record under a
  profiler session that the program did not start.
"""
import json
import logging
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.transformer import init_model
from repro.nn.params import unbox
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.obs.metrics import MirroredCounts, NULL_REGISTRY, Registry
from repro.obs.tracing import Tracer, chrome_trace, validate_spans
from repro.serving_engine import (Engine, FaultInjector, FaultSpec,
                                  Request, Scheduler)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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


# ============================================================== registry
def test_counter_inc_and_labels():
    reg = Registry()
    c = reg.counter("req_total", "requests", ("status",))
    c.labels(status="ok").inc()
    c.labels(status="ok").inc(2)
    c.labels(status="error").inc()
    assert c.get(status="ok") == 3
    assert c.get(status="error") == 1
    # same label set memoises to the same child
    assert c.labels(status="ok") is c.labels(status="ok")
    with pytest.raises(ValueError):
        c.labels(status="ok").inc(-1)       # counters are monotone
    with pytest.raises(ValueError):
        c.labels(wrong="x")                 # undeclared label name


def test_gauge_and_histogram_semantics():
    reg = Registry()
    g = reg.gauge("depth")
    g.set(5)
    g.dec(2)
    g.inc()
    assert g.get() == 4
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for x in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(x)
    ch = h.labels()
    # cumulative le semantics: bucket[i] counts every x <= le
    assert ch.bucket_counts == [1, 3, 4]
    assert ch.count == 5 and ch.sum == pytest.approx(56.05)
    with pytest.raises(TypeError):
        g.observe(1.0)
    with pytest.raises(TypeError):
        h.set(1.0)


def test_registration_idempotent_and_collision():
    reg = Registry()
    a = reg.counter("x_total", "x")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError):
        reg.gauge("x_total")                       # kind collision
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("a",))  # labelnames collision
    with pytest.raises(ValueError):
        reg.counter("bad name")                    # exposition identifier


def test_render_prometheus_exposition():
    reg = Registry()
    reg.counter("req_total", "requests served", ("code",)).labels(
        code="200").inc(17)
    reg.gauge("depth", "queue depth").set(3)
    reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.render_prometheus()
    lines = text.strip().splitlines()
    assert "# HELP req_total requests served" in lines
    assert "# TYPE req_total counter" in lines
    assert 'req_total{code="200"} 17' in lines
    assert "# TYPE lat_seconds histogram" in lines
    assert 'lat_seconds_bucket{le="0.1"} 0' in lines
    assert 'lat_seconds_bucket{le="1"} 1' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 1' in lines
    assert "lat_seconds_count 1" in lines
    assert any(ln.startswith("lat_seconds_sum 0.5") for ln in lines)
    # every non-comment line is "name[{labels}] value"
    for ln in lines:
        if not ln.startswith("#"):
            assert len(ln.rsplit(" ", 1)) == 2, ln


def test_json_dump_roundtrip(tmp_path):
    reg = Registry()
    reg.counter("n_total").inc(4)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    path = tmp_path / "m.json"
    reg.dump_json(str(path))
    data = json.loads(path.read_text())
    assert data["metrics"]["n_total"]["series"][0]["value"] == 4
    h = data["metrics"]["h"]["series"][0]
    assert h["counts"] == [1] and h["count"] == 1


def test_null_registry_is_noop(tmp_path):
    c = NULL_REGISTRY.counter("anything", "x", ("a",))
    c.inc()
    c.labels(a="b").inc()
    c.observe(3.0)        # no kind checking on the shared noop: all quiet
    assert c.get() == 0.0
    assert NULL_REGISTRY.render_prometheus() == ""
    NULL_REGISTRY.dump_json(str(tmp_path / "m.json"))
    assert json.loads((tmp_path / "m.json").read_text())["metrics"] == {}


def test_default_registry_env_gate(monkeypatch):
    obs_metrics.set_default_registry(None)
    monkeypatch.delenv("REPRO_METRICS", raising=False)
    try:
        assert obs_metrics.default_registry() is NULL_REGISTRY
        obs_metrics.set_default_registry(None)
        monkeypatch.setenv("REPRO_METRICS", "1")
        reg = obs_metrics.default_registry()
        assert isinstance(reg, Registry)
        assert obs_metrics.default_registry() is reg     # sticky
    finally:
        obs_metrics.set_default_registry(None)


def test_registry_thread_safety():
    reg = Registry()
    c = reg.counter("n_total", "x", ("t",))
    h = reg.histogram("h_seconds")

    def work(tid):
        for _ in range(1000):
            c.labels(t=str(tid % 2)).inc()
            h.observe(0.01)
    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.get(t="0") + c.get(t="1") == 8000
    assert h.get() == 8000


def test_mirrored_counts():
    reg = Registry()
    c = reg.counter("traces_total", "x", ("fn",))
    d = MirroredCounts({"a": 0, "b": 0}, c, "fn")
    d["a"] += 1
    d["a"] += 2
    d["b"] += 1
    assert d == {"a": 3, "b": 1}                   # dict reads unchanged
    assert c.get(fn="a") == 3 and c.get(fn="b") == 1
    d["a"] = 0                                     # resets never decrement
    assert c.get(fn="a") == 3


# ================================================================ tracer
def test_tracer_jsonl_stream_and_chrome(tmp_path):
    path = tmp_path / "t.jsonl"
    clk = {"t": 0.0}

    def clock():
        clk["t"] += 0.25
        return clk["t"]

    tr = Tracer(str(path), clock=clock)
    tr.begin("request", "u1", prompt_len=4)
    tr.begin("queue", "u1")
    tr.end("queue", "u1")
    tr.instant("first_token", "u1")
    tr.counter("queue_depth", 2)
    tr.end("request", "u1", status="ok")
    tr.close()
    loaded = obs_tracing.load_jsonl(str(path))
    assert loaded == tr.events
    spans = validate_spans(loaded)
    assert spans["u1"][0]["status"] == "ok"
    assert spans["u1"][0]["children"] == {"queue": 1, "first_token": 1}

    chrome = chrome_trace(loaded)
    evs = chrome["traceEvents"]
    # pid/ts on every event; engine + one request thread, both named
    assert all("pid" in e and "ph" in e for e in evs)
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert names == {"engine", "req u1"}
    ph = [e["ph"] for e in evs if e.get("cat") == "serving"]
    assert ph == ["B", "B", "E", "i", "C", "E"]
    # timestamps rebased to first event and scaled to µs
    ts = [e["ts"] for e in evs if e.get("cat") == "serving"]
    assert ts[0] == 0 and ts[1] == pytest.approx(0.25e6)
    json.dumps(chrome)                             # serialisable as-is


def test_validate_spans_rejects_incomplete():
    t0 = {"ts": 0.0, "ph": "B", "name": "request", "uid": "u"}
    with pytest.raises(ValueError, match="unclosed"):
        validate_spans([t0])
    with pytest.raises(ValueError, match="non-terminal"):
        validate_spans([t0, {"ts": 1.0, "ph": "E", "name": "request",
                             "uid": "u"}])
    with pytest.raises(ValueError, match="no queue span"):
        validate_spans([t0, {"ts": 1.0, "ph": "E", "name": "request",
                             "uid": "u", "attrs": {"status": "ok"}}])
    with pytest.raises(ValueError, match="end without begin"):
        validate_spans([{"ts": 0.0, "ph": "E", "name": "prefill",
                         "uid": "u"}])
    with pytest.raises(ValueError, match="re-begun"):
        validate_spans([t0, dict(t0)])


# ===================================================== scheduler + spans
def test_scheduler_span_tree_packed_and_async_detok(env):
    """A real run (packed admission, async detok callbacks) leaves one
    complete span tree per request: queue -> prefill -> decode children,
    first_token + (max_new - 1) token instants, status ok — and the
    registry's TTFT/prefill/step series agree with scheduler stats."""
    reg = Registry()
    tr = Tracer()
    eng = Engine(env["cfg"], env["params"], slots=2, max_len=MAX_LEN,
                 metrics=reg)
    streamed = {}
    sched = Scheduler(eng, metrics=reg, tracer=tr, detok_async=True)
    for r in _fleet(env["prompts"],
                    on_token=lambda u, t: streamed.setdefault(u, [])
                    .append(t)):
        sched.submit(r)
    results, _ = sched.run()

    spans = validate_spans(tr.events)
    assert sorted(spans) == [f"r{i}" for i in range(len(PLENS))]
    for i, g in enumerate(GENS):
        recs = spans[f"r{i}"]
        assert len(recs) == 1
        rec = recs[0]
        assert rec["status"] == "ok"
        assert rec["children"]["queue"] == 1
        assert rec["children"]["prefill"] == 1
        assert rec["children"]["decode"] == 1
        assert rec["tokens"] == g == len(results[f"r{i}"])
        assert rec["children"]["first_token"] == 1
    # ordering within each uid's track: queue closes before prefill opens
    for i in range(len(PLENS)):
        uid = f"r{i}"
        seq = [(e["ph"], e["name"]) for e in tr.events
               if e.get("uid") == uid]
        assert seq.index(("E", "queue")) < seq.index(("B", "prefill"))
        assert seq.index(("E", "prefill")) < seq.index(("B", "decode"))
        assert seq[-1] == ("E", "request")
    # packed admission was traced as such (2 slots -> first wave packs 2)
    packed = [e for e in tr.events if e["name"] == "prefill"
              and e["ph"] == "B" and e.get("attrs", {}).get("packed")]
    assert len(packed) >= 2
    # registry cross-checks
    assert reg.get("repro_requests_submitted_total").get() == len(PLENS)
    assert reg.get("repro_requests_finished_total").get(
        status="ok") == len(PLENS)
    assert reg.get("repro_ttft_seconds").get() == len(PLENS)
    assert reg.get("repro_decode_steps_total").get() == sched.steps
    assert reg.get("repro_decode_step_seconds").get() == sched.steps
    assert reg.get("repro_packed_prefill_waves_total").get() == \
        sched.packed_prefills
    by_mode = reg.get("repro_prefills_total")
    assert (by_mode.get(mode="packed") + by_mode.get(mode="single")
            == sched.prefills)
    # engine trace_counts mirrored under the same registry
    traces = reg.get("repro_engine_traces_total")
    assert traces.get(fn="generate") == eng.trace_counts["generate"] >= 1
    # async detok settled: callbacks saw every token
    for uid, toks in results.items():
        assert streamed[uid] == toks


def test_chaos_run_spans_and_fault_tags(env):
    """Scripted faults land as tagged trace instants + labeled counters;
    the poisoned request's span tree ends status=error, survivors ok."""
    reg = Registry()
    tr = Tracer()
    eng = Engine(env["cfg"], env["params"], slots=2, max_len=MAX_LEN)
    inj = FaultInjector(specs=[
        FaultSpec(site="prefill", uid="r1", count=99),   # persistent
        FaultSpec(site="decode", at=1),                  # transient
    ])
    sched = Scheduler(eng, injector=inj, metrics=reg, tracer=tr,
                      backoff_base=0.0, max_retries=2)
    for r in _fleet(env["prompts"]):
        sched.submit(r)
    results, _ = sched.run()

    spans = validate_spans(tr.events)
    statuses = {u: recs[-1]["status"] for u, recs in spans.items()}
    for uid, o in sched.outcomes.items():
        assert statuses[uid] == o.status   # trace terminus == Outcome
    assert statuses["r1"] == "error"
    assert sum(s == "ok" for s in statuses.values()) == len(PLENS) - 1

    faults = [e for e in tr.events if e["name"] == "fault"]
    assert len(faults) == inj.fired == 4   # 3 prefill (retries) + 1 decode
    prefill_faults = [e for e in faults
                     if e["attrs"]["site"] == "prefill"]
    assert all(e["uid"] == "r1" and e["attrs"]["spec"] == "spec0"
               and e["attrs"]["action"] == "raise"
               for e in prefill_faults)
    retries = [e for e in tr.events if e["name"] == "retry"]
    assert len(retries) == sched.retries == 3
    assert reg.get("repro_faults_injected_total").get(
        site="prefill", action="raise", spec="spec0") == 3
    assert reg.get("repro_retries_total").get(site="prefill") == 2
    assert reg.get("repro_retries_total").get(site="decode") == 1
    assert reg.get("repro_requests_finished_total").get(status="error") == 1


def test_preempt_closes_spans_and_restore_resumes(env, tmp_path):
    """preempt() ends every open span with status=preempted; a restored
    scheduler sharing the tracer re-begins them (resumed=True) and the
    combined trace validates with every request ending ok."""
    reg = Registry()
    tr = Tracer()
    snap = str(tmp_path / "snap")
    eng = Engine(env["cfg"], env["params"], slots=2, max_len=MAX_LEN)
    sched = Scheduler(eng, metrics=reg, tracer=tr, snapshot_dir=snap)
    n = {"tok": 0}

    def kill_soon(u, t):
        n["tok"] += 1
        if n["tok"] == 5:
            sched.preempt()
    for r in _fleet(env["prompts"], on_token=kill_soon):
        sched.submit(r)
    sched.run()
    assert sched.preempted
    spans = validate_spans(tr.events)        # complete despite preemption
    pre = {u: recs[-1]["status"] for u, recs in spans.items()}
    assert "preempted" in pre.values()
    assert reg.get("repro_requests_finished_total").get(
        status="preempted") == 0   # preemption is not a _finish

    sched2 = Scheduler(eng, metrics=reg, tracer=tr, snapshot_dir=snap)
    assert sched2.try_restore()
    results, _ = sched2.run()
    spans = validate_spans(tr.events)
    for i, g in enumerate(GENS):
        recs = spans[f"r{i}"]
        assert recs[-1]["status"] == "ok"
        # token-exact across the preemption: instants sum to the budget
        assert sum(r["tokens"] for r in recs) == g
        if len(recs) > 1:                     # resumed requests re-begun
            assert recs[-1]["attrs"].get("resumed") is True
        assert len(results[f"r{i}"]) == g


def test_expired_request_span(env):
    clk = {"t": 0.0}

    def tick(u, t):
        clk["t"] += 1.0
    reqs = _fleet(env["prompts"][:2], gens=[10, 10], on_token=tick)
    reqs[0].deadline = 5.0
    reg = Registry()
    tr = Tracer()
    sched = Scheduler(Engine(env["cfg"], env["params"], slots=2,
                             max_len=MAX_LEN),
                      clock=lambda: clk["t"], backoff_base=0.0,
                      metrics=reg, tracer=tr)
    for r in reqs:
        sched.submit(r)
    sched.run()
    assert sched.outcomes["r0"].status == "expired"
    spans = validate_spans(tr.events)
    assert spans["r0"][-1]["status"] == "expired"
    assert spans["r0"][-1]["children"].get("expired") == 1
    assert reg.get("repro_evictions_total").get(reason="deadline") == 1


# ============================================================== trainer
def test_trainer_metrics(tmp_path):
    from repro.data.pipeline import DataConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    def train_step(state, batch):
        return state + 1, {"loss": 1.0 / (state + 1.0)}

    reg = Registry()
    boom = {"armed": True}

    def failure_hook(step, attempt):
        if step == 2 and attempt == 0 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected")

    cfg = TrainerConfig(total_steps=5, max_retries=1,
                        undonated_retry_copy=False, log_every=0)
    tr = Trainer(cfg, train_step,
                 DataConfig(vocab=16, global_batch=2, seq_len=4, seed=0),
                 failure_hook=failure_hook, metrics=reg)
    state, step = tr.run(jax.numpy.float32(0.0))
    assert step == 5
    assert reg.get("repro_train_steps_total").get() == 5
    assert reg.get("repro_train_retries_total").get() == 1
    assert reg.get("repro_train_step_seconds").get() == 5
    assert reg.get("repro_train_loss").get() > 0


# ================================================================ logger
def test_log_level_knob(monkeypatch):
    monkeypatch.delenv("REPRO_LOG_LEVEL", raising=False)
    assert obs_log.default_level() == logging.WARNING   # under pytest
    monkeypatch.setenv("REPRO_LOG_LEVEL", "DEBUG")
    assert obs_log.default_level() == logging.DEBUG
    monkeypatch.setenv("REPRO_LOG_LEVEL", "15")
    assert obs_log.default_level() == 15
    monkeypatch.setenv("REPRO_LOG_LEVEL", "bogus")
    with pytest.raises(ValueError):
        obs_log.default_level()


def test_logger_emits_and_set_level():
    import io
    lg = obs_log.get_logger("testsub")
    root = obs_log.get_logger()
    buf = io.StringIO()
    h = logging.StreamHandler(buf)
    h.setFormatter(root.handlers[0].formatter)  # the [repro.<sub>] format
    root.addHandler(h)
    obs_log.set_level("INFO")
    try:
        lg.info("hello from obs")
        assert "[repro.testsub] hello from obs" in buf.getvalue()
        obs_log.set_level(logging.WARNING)
        lg.info("now below level")
        assert "now below level" not in buf.getvalue()
    finally:
        root.removeHandler(h)
        obs_log.set_level(obs_log.default_level())
    with pytest.raises(ValueError):
        obs_log.set_level("NOT_A_LEVEL")


def test_scheduler_default_log_is_quiet_under_pytest(env, capsys):
    sched = Scheduler(Engine(env["cfg"], env["params"], slots=2,
                             max_len=MAX_LEN))
    sched.log("should not appear on stdout")    # INFO < WARNING: dropped
    out = capsys.readouterr()
    assert "should not appear" not in out.out


# ============================================================ obs_report
def test_obs_report_cli(tmp_path, env):
    reg = Registry()
    trace_path = tmp_path / "t.jsonl"
    tr = Tracer(str(trace_path))
    sched = Scheduler(Engine(env["cfg"], env["params"], slots=2,
                             max_len=MAX_LEN),
                      metrics=reg, tracer=tr)
    for r in _fleet(env["prompts"][:2], gens=[4, 5]):
        sched.submit(r)
    sched.run()
    tr.close()
    prom = tmp_path / "m.prom"
    reg.dump_prometheus(str(prom))

    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "obs_report.py"),
         "--trace", str(trace_path), "--metrics", str(prom),
         "--chrome", str(tmp_path / "t.chrome.json"), "--check"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "complete request span trees" in r.stdout
    chrome = json.loads((tmp_path / "t.chrome.json").read_text())
    assert chrome["traceEvents"]

    # the human report renders both artifacts
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "obs_report.py"),
         "--trace", str(trace_path), "--metrics", str(prom)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TTFT" in r.stdout and "repro_requests_submitted_total" in r.stdout

    # --check fails loudly on a truncated trace (killed-process prefix
    # with a dangling span)
    bad = tmp_path / "bad.jsonl"
    lines = trace_path.read_text().strip().splitlines()
    bad.write_text("\n".join(lines[:3]) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "obs_report.py"),
         "--trace", str(bad), "--check"],
        capture_output=True, text=True)
    assert r.returncode == 1 and "FAIL" in r.stdout


# ============================================================= profiling
def test_profiling_noop_without_env(monkeypatch):
    from repro.obs import profiling as obs_prof
    monkeypatch.delenv("REPRO_PROFILE_DIR", raising=False)
    with obs_prof.session("x") as started:
        assert started is False
    with obs_prof.annotation("y"):
        pass


def test_profiling_session_writes_trace(monkeypatch, tmp_path):
    from repro.obs import profiling as obs_prof
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path))
    with obs_prof.session("x") as started:
        if not started:           # profiler unavailable in this build
            pytest.skip("jax.profiler could not start")
        with obs_prof.annotation("region"):
            jax.numpy.zeros(8).block_until_ready()
    assert any(tmp_path.rglob("*"))    # something was written


def _spans(logdir, prefix="repro."):
    """Names of the host events under ``prefix`` in the newest profile
    written under ``logdir``, in the order they started."""
    from jax.profiler import ProfileData
    path = sorted(logdir.rglob("*.xplane.pb"), key=os.path.getmtime)[-1]
    evs = [ev for pl in ProfileData.from_file(str(path)).planes
           if not pl.name.startswith("/device:")
           for ln in pl.lines for ev in ln.events
           if ev.name.startswith(prefix)]
    return [ev.name for ev in sorted(evs, key=lambda e: e.start_ns)]


def test_annotation_records_under_any_profiler_session(monkeypatch,
                                                      tmp_path):
    """No REPRO_PROFILE_DIR: a session started by someone else (here
    jax.profiler directly, as a benchmark's capture does) records the
    span under its name as written; outside a session nothing is
    recorded."""
    from repro.obs import profiling as obs_prof
    monkeypatch.delenv("REPRO_PROFILE_DIR", raising=False)
    with obs_prof.annotation("repro.test.before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_prof.annotation("repro.test.region"):
            jax.numpy.zeros(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert _spans(tmp_path) == ["repro.test.region"]


def test_annotation_open_when_the_session_stops_is_not_recorded(
        monkeypatch, tmp_path):
    """A span still open when the profiler stops is lost whole: a reader
    of the trace never sees a part of it."""
    from repro.obs import profiling as obs_prof
    monkeypatch.delenv("REPRO_PROFILE_DIR", raising=False)
    jax.profiler.start_trace(str(tmp_path))
    still = obs_prof.annotation("repro.test.open")
    try:
        with obs_prof.annotation("repro.test.closed"):
            pass
        still.__enter__()
    finally:
        jax.profiler.stop_trace()
    still.__exit__(None, None, None)
    assert _spans(tmp_path) == ["repro.test.closed"]


@pytest.mark.parametrize("backup,donate,copies", [
    (True, True, [1, 1, 1]),
    (True, False, [1, 0, 0]),
    (False, False, [0, 0, 0]),
], ids=["donating", "undonated", "off"])
def test_trainer_spans_once_per_step_in_order(backup, donate, copies,
                                              monkeypatch, tmp_path):
    """Under a session started outside the program, each step records its
    batch read, batch placement, state copy (only where the host backup
    is kept: on the first step, and then on every step while the step
    donates its state) and step, in that order."""
    from repro.data.pipeline import DataConfig
    from repro.runtime.trainer import Trainer, TrainerConfig
    monkeypatch.delenv("REPRO_PROFILE_DIR", raising=False)

    def train_step(state, batch):
        return state + 1, {"loss": 1.0 / (state + 1.0)}

    if donate:
        train_step = jax.jit(train_step, donate_argnums=0)
    reg = Registry()
    tr = Trainer(TrainerConfig(total_steps=3, log_every=0,
                               undonated_retry_copy=backup),
                 train_step,
                 DataConfig(vocab=16, global_batch=2, seq_len=4, seed=0),
                 metrics=reg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, done = tr.run(jax.numpy.float32(0.0))
    finally:
        jax.profiler.stop_trace()
    assert done == 3
    spans = []
    for n in copies:
        spans += ["repro.train.read_batch", "repro.train.put_batch",
                  *(["repro.train.state_copy"] * n), "repro.train.step"]
    assert _spans(tmp_path, "repro.train.") == spans
    assert reg.get("repro_train_state_copies_total").get() == sum(copies)
