"""Render serving observability artifacts into a human report (CLI).

Consumes the two ISSUE 9 artifact kinds:

* a **metrics dump** — the Prometheus text exposition or JSON file
  written by ``--metrics-file`` (launch/serve.py, launch/train.py, examples)
  or ``Registry.dump_*``;
* a **trace file** — the JSONL span stream written by ``--trace-file``
  or ``REPRO_TRACE_FILE``.

and prints a latency/throughput summary (request counts by terminal
status, TTFT/TPOT/step-time percentiles reconstructed from spans,
fault/retry tallies). Also the artifact Swiss-army knife for CI:

    python tools/obs_report.py --trace t.jsonl --metrics m.prom
    python tools/obs_report.py --trace t.jsonl --check     # validate only
    python tools/obs_report.py --trace t.jsonl --chrome out.json

``--check`` exits non-zero unless every request span tree is complete
(every begin ended, terminal status present, queue child present) —
the machine contract from :func:`repro.obs.tracing.validate_spans`;
the serve/chaos CI smoke jobs gate on it. ``--chrome`` converts the
JSONL to Chrome ``trace_event`` JSON for chrome://tracing / Perfetto.

ISSUE 10 views:

* TTFT/TPOT p50/p99 are derived from the registry **histograms** as
  well as from spans whenever both artifacts are given, and any
  disagreement beyond the containing bucket's width is flagged
  (``DISAGREE``) — the cheap cross-check that catches histogram
  mirroring bugs.
* ``--kernels`` — the kernel-tier table: dispatch counts
  (``repro_kernel_dispatch_total``) and compile watchdog counts
  (``repro_compiles_total`` + compile-seconds histogram). Kernel device
  time is read from a chip trace by the benchmark (``bench/``), not here.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.obs import tracing  # noqa: E402


def _pct(xs, q):
    if not xs:
        return float("nan")
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, int(round(q / 100 * (len(xs) - 1)))))
    return xs[i]


def _fmt_s(x):
    return "-" if x != x else (f"{x * 1e3:.2f}ms" if x < 1 else f"{x:.3f}s")


def report_trace(events) -> dict:
    """Span-derived serving report: terminal statuses, per-request
    TTFT (request begin → first_token), inter-token gaps, decode-step
    walls, fault/retry instants."""
    spans = tracing.validate_spans(events)
    statuses: dict = {}
    ttft, tpot, steps, faults, retries = [], [], [], 0, 0
    # per-uid instant timestamps for TTFT/TPOT reconstruction
    first_tok: dict = {}
    last_tok: dict = {}
    for ev in events:
        name, uid, ts = ev["name"], ev.get("uid"), ev["ts"]
        if ev["ph"] == "i":
            if name == "first_token" and uid is not None:
                first_tok.setdefault(uid, ts)
                last_tok[uid] = ts
            elif name == "token" and uid is not None:
                if uid in last_tok:
                    tpot.append(ts - last_tok[uid])
                last_tok[uid] = ts
            elif name == "fault":
                faults += 1
            elif name == "retry":
                retries += 1
        elif ev["ph"] == "E" and name == "step":
            pass
    # step walls from B/E pairs on the global track
    open_step = []
    for ev in events:
        if ev["name"] != "step":
            continue
        if ev["ph"] == "B":
            open_step.append(ev["ts"])
        elif ev["ph"] == "E" and open_step:
            steps.append(ev["ts"] - open_step.pop())
    n_spans = 0
    for uid, recs in spans.items():
        for rec in recs:
            n_spans += 1
            statuses[rec["status"]] = statuses.get(rec["status"], 0) + 1
            if uid in first_tok and first_tok[uid] >= rec["t0"] and (
                    rec["t1"] is None or first_tok[uid] <= rec["t1"]):
                ttft.append(first_tok[uid] - rec["t0"])
    return {
        "requests": len(spans), "request_spans": n_spans,
        "statuses": statuses,
        "ttft": ttft, "tpot": tpot, "step": steps,
        "faults": faults, "retries": retries,
    }


def load_metrics(path: str) -> dict:
    """Parse a metrics dump — JSON (Registry.dump_json) or the
    Prometheus text exposition — into {metric_name: [(labels, value)]}
    (histograms keep their _bucket/_sum/_count sample names)."""
    text = pathlib.Path(path).read_text()
    out: dict = {}
    if path.endswith(".json"):
        data = json.loads(text).get("metrics", {})
        for name, m in data.items():
            for s in m.get("series", []):
                labels = s.get("labels", {})
                if "value" in s:
                    out.setdefault(name, []).append((labels, s["value"]))
                else:                      # histogram series
                    out.setdefault(name + "_count", []).append(
                        (labels, float(s.get("count", 0))))
                    out.setdefault(name + "_sum", []).append(
                        (labels, float(s.get("sum", 0.0))))
        return out
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        if "{" in head:
            name, _, rest = head.partition("{")
            labels = dict(p.split("=", 1) for p in
                          rest.rstrip("}").split(",") if "=" in p)
            labels = {k: v.strip('"') for k, v in labels.items()}
        else:
            name, labels = head, {}
        out.setdefault(name, []).append((labels, float(val)))
    return out


def load_histograms(path: str) -> dict:
    """Parse histogram structure out of a metrics dump:
    ``{name: [(labels, buckets, cum_counts, sum, count)]}`` with
    ``buckets`` the finite ``le`` edges and ``cum_counts`` cumulative
    (Prometheus semantics), total in ``count``."""
    text = pathlib.Path(path).read_text()
    out: dict = {}
    if path.endswith(".json"):
        data = json.loads(text).get("metrics", {})
        for name, m in data.items():
            if m.get("kind") != "histogram":
                continue
            for s in m.get("series", []):
                out.setdefault(name, []).append(
                    (s.get("labels", {}), list(s["buckets"]),
                     list(s["counts"]), float(s.get("sum", 0.0)),
                     int(s.get("count", 0))))
        return out
    # prometheus text: group _bucket/_sum/_count by (name, labels\le)
    acc: dict = {}
    for labels_name, rows in load_metrics(path).items():
        for suffix in ("_bucket", "_sum", "_count"):
            if labels_name.endswith(suffix):
                base = labels_name[: -len(suffix)]
                for labels, val in rows:
                    key_labels = {k: v for k, v in labels.items()
                                  if k != "le"}
                    key = (base, tuple(sorted(key_labels.items())))
                    rec = acc.setdefault(
                        key, {"labels": key_labels, "edges": [],
                              "sum": 0.0, "count": 0})
                    if suffix == "_bucket":
                        le = labels.get("le", "+Inf")
                        if le != "+Inf":
                            rec["edges"].append((float(le), val))
                    elif suffix == "_sum":
                        rec["sum"] = val
                    else:
                        rec["count"] = int(val)
                break
    for (base, _), rec in acc.items():
        edges = sorted(rec["edges"])
        out.setdefault(base, []).append(
            (rec["labels"], [e for e, _ in edges],
             [int(c) for _, c in edges], rec["sum"], rec["count"]))
    return out


def hist_quantile(buckets, cum_counts, count, q):
    """Quantile from cumulative bucket counts: linear interpolation
    inside the containing bucket. Returns ``(value, lo, hi)`` where
    [lo, hi) is the containing bucket (hi = inf for the overflow
    bucket — observations above every edge). NaNs when empty."""
    nan = float("nan")
    if count <= 0 or not buckets:
        return nan, nan, nan
    target = q / 100.0 * count
    prev_edge, prev_cum = 0.0, 0
    for edge, cum in zip(buckets, cum_counts):
        if cum >= target:
            frac = ((target - prev_cum) / (cum - prev_cum)
                    if cum > prev_cum else 1.0)
            return prev_edge + frac * (edge - prev_edge), prev_edge, edge
        prev_edge, prev_cum = edge, cum
    return buckets[-1], buckets[-1], float("inf")


def compare_latency(trace_report: dict, hists: dict) -> list:
    """Span-derived vs histogram-derived TTFT/TPOT p50/p99 (ISSUE 10
    satellite): rows ``{"metric", "q", "span_s", "hist_s", "width_s",
    "agree"}``. ``agree`` is False when the two differ by more than the
    width of the histogram bucket containing the quantile — the
    histogram cannot localise finer than its bucket, so anything within
    one width is indistinguishable; beyond it the mirroring is broken."""
    pairs = (("ttft", "repro_ttft_seconds"),
             ("tpot", "repro_tpot_seconds"))
    rows = []
    for key, metric in pairs:
        series = hists.get(metric)
        xs = trace_report.get(key) or []
        if not series or not xs:
            continue
        labels, buckets, cum, _sum, count = series[0]
        for q in (50, 99):
            hv, lo, hi = hist_quantile(buckets, cum, count, q)
            sv = _pct(xs, q)
            width = (hi - lo) if hi != float("inf") else float("inf")
            agree = not (abs(sv - hv) > width) \
                if sv == sv and hv == hv else True
            rows.append({"metric": key, "q": q, "span_s": sv,
                         "hist_s": hv, "width_s": width, "agree": agree})
    return rows


def print_kernel_report(metrics_path, out=print) -> None:
    """The ``--kernels`` view: dispatch counts and compile watchdog
    counts."""
    m = load_metrics(metrics_path)
    hists = load_histograms(metrics_path)

    def rows_of(name):
        return m.get(name, [])

    out("kernel tier:")
    disp = rows_of("repro_kernel_dispatch_total")
    if disp:
        out("  dispatches (kernel, source -> count):")
        for labels, val in sorted(disp, key=lambda r: (
                r[0].get("kernel", ""), r[0].get("source", ""))):
            out(f"    {labels.get('kernel', '?'):16s} "
                f"{labels.get('source', '?'):10s} {val:g}")
    comp = rows_of("repro_compiles_total")
    if comp:
        out("  compiles (fn -> traces):")
        for labels, val in sorted(comp, key=lambda r: r[0].get("fn", "")):
            out(f"    {labels.get('fn', '?'):32s} {val:g}")
    ch = hists.get("repro_compile_seconds")
    if ch:
        tot_s = sum(s for _, _, _, s, _ in ch)
        tot_n = sum(n for _, _, _, _, n in ch)
        out(f"  compile wall: {tot_n} timed traces, {tot_s:.3f}s total")
    if not (disp or comp):
        out("  (no kernel-tier series in this dump)")


def print_report(trace_path=None, metrics_path=None, out=print):
    r = None
    if trace_path:
        events = tracing.load_jsonl(trace_path)
        r = report_trace(events)
        out(f"trace: {trace_path} ({len(events)} events)")
        out(f"  requests: {r['requests']} uids, {r['request_spans']} "
            f"span trees; statuses={r['statuses']}")
        for key, label in (("ttft", "TTFT"), ("tpot", "TPOT"),
                           ("step", "decode step")):
            xs = r[key]
            out(f"  {label}: n={len(xs)} p50={_fmt_s(_pct(xs, 50))} "
                f"p90={_fmt_s(_pct(xs, 90))} p99={_fmt_s(_pct(xs, 99))}")
        out(f"  faults injected: {r['faults']}; retries: {r['retries']}")
    if metrics_path:
        m = load_metrics(metrics_path)
        out(f"metrics: {metrics_path} ({len(m)} series)")
        for name in sorted(m):
            if name.endswith(("_bucket", "_sum")):
                continue
            for labels, val in m[name]:
                lbl = ("{" + ",".join(f"{k}={v}" for k, v in
                                      sorted(labels.items())) + "}"
                       if labels else "")
                out(f"  {name}{lbl} = {val:g}")
        # histogram-derived latency + span cross-check (ISSUE 10)
        hists = load_histograms(metrics_path)
        for metric, label in (("repro_ttft_seconds", "TTFT"),
                              ("repro_tpot_seconds", "TPOT")):
            series = hists.get(metric)
            if not series:
                continue
            _, buckets, cum, _s, count = series[0]
            p50, _, _ = hist_quantile(buckets, cum, count, 50)
            p99, _, _ = hist_quantile(buckets, cum, count, 99)
            out(f"  {label} (histogram): n={count} "
                f"p50={_fmt_s(p50)} p99={_fmt_s(p99)}")
        if r is not None:
            disagreements = 0
            for row in compare_latency(r, hists):
                mark = "ok" if row["agree"] else "DISAGREE"
                if not row["agree"]:
                    disagreements += 1
                out(f"  {row['metric']} p{row['q']}: "
                    f"span={_fmt_s(row['span_s'])} "
                    f"hist={_fmt_s(row['hist_s'])} "
                    f"(bucket width {_fmt_s(row['width_s'])}) {mark}")
            if disagreements:
                out(f"  WARNING: {disagreements} span-vs-histogram "
                    "disagreement(s) beyond one bucket width — check "
                    "metric mirroring")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarise / validate / convert obs artifacts")
    ap.add_argument("--trace", default=None,
                    help="span JSONL (REPRO_TRACE_FILE / --trace-file)")
    ap.add_argument("--metrics", default=None,
                    help="metrics dump (.prom text exposition or .json)")
    ap.add_argument("--chrome", default=None, metavar="OUT",
                    help="also write the trace as Chrome trace_event "
                         "JSON (Perfetto-loadable)")
    ap.add_argument("--check", action="store_true",
                    help="validate span completeness only; exit 1 on any "
                         "violation (CI smoke gate)")
    ap.add_argument("--kernels", action="store_true",
                    help="print the kernel-tier table (dispatch counts, "
                         "compile watchdog) from --metrics")
    args = ap.parse_args(argv)
    if not args.trace and not args.metrics:
        ap.error("nothing to do: pass --trace and/or --metrics")
    if args.kernels and not args.metrics:
        ap.error("--kernels needs --metrics")
    if (args.chrome or args.check) and not args.trace:
        ap.error("--chrome/--check need --trace")
    if args.check:
        events = tracing.load_jsonl(args.trace)
        try:
            spans = tracing.validate_spans(events)
        except ValueError as e:
            print(f"[obs-report] FAIL: {e}")
            return 1
        n = sum(len(v) for v in spans.values())
        print(f"[obs-report] OK: {len(spans)} uids, {n} complete "
              f"request span trees")
        if args.chrome:
            tracing.write_chrome(events, args.chrome)
            print(f"[obs-report] chrome trace: {args.chrome}")
        return 0
    print_report(args.trace, args.metrics)
    if args.kernels:
        print_kernel_report(args.metrics)
    if args.chrome:
        tracing.write_chrome(tracing.load_jsonl(args.trace), args.chrome)
        print(f"[obs-report] chrome trace: {args.chrome}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
