"""Device trace: capture a short window with JAX's profiler, flatten the
``.xplane.pb`` into plain events, and reduce those to the numbers the
per-layer metrics and the breakdown read.

A flat event is a dict: ``pl`` plane name, ``ln`` line name, ``name``,
``t`` start and ``d`` duration in nanoseconds (one timebase for host and
device planes). Device planes are named ``/device:TPU:<i>``; their ops sit on
the line named in ``OPS_LINE`` and the programs that ran them on
``MODULES_LINE``. The harness marks the traced window with a host
annotation named ``WINDOW`` and its own phases with names starting
``bench.``.

A TPU op event carries no op metadata: its name is the text of its HLO
instruction (``%fusion.12 = f32[...] fusion(...)``). Which ops belong to
a ``jax.named_scope`` is read from the program's optimised HLO text
(:func:`scope_ops`), where each instruction's metadata names its scope.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_PREFIX = "bench."
NAME_CHARS = 200
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all"
    r"|allgather|reducescatter|allreduce", re.I)


# ------------------------------------------------------------------ load
def newest_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def flatten(path: str) -> list:
    """Device events of every line, and the harness's host annotations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIX):
                    continue
                # an op's name is its whole HLO instruction; the head
                # names it
                out.append({"pl": plane.name, "ln": line.name,
                            "name": ev.name[:NAME_CHARS],
                            "t": float(ev.start_ns),
                            "d": float(ev.duration_ns)})
    return out


# ---------------------------------------------------------- intervals
def union(intervals) -> list:
    """Merge (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """Merged intervals a minus merged intervals b."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _inside(evs, runs) -> list:
    """The events that lie within one of the merged intervals ``runs``."""
    starts = [s for s, _ in runs]
    out = []
    for e in evs:
        i = bisect.bisect_right(starts, e["t"]) - 1
        if i >= 0 and e["t"] + e["d"] <= runs[i][1]:
            out.append(e)
    return out


# ------------------------------------------------------------ HLO scopes
_COMP = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_CALLS = re.compile(r"\bcalls=%([^\s,)]+)")
#: ops whose events span the ops of their bodies, which have their own
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def instruction(event_name: str) -> str:
    """The HLO instruction name of a TPU op event (``%fusion.12 = ...``)."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def module_name(hlo_text: str) -> str:
    """The program's name as the trace's module line shows it."""
    first = hlo_text.lstrip().split("\n", 1)[0]
    if not first.startswith("HloModule "):
        raise ValueError("not an HLO module's text")
    return first.split()[1].rstrip(",")


def op_names(hlo_text: str) -> dict:
    """{instruction: the JAX op it came from} for the instructions of an
    HLO module whose metadata says, the jit prefix left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        o = re.search(r'op_name="([^"]*)"', line) if m else None
        if o:
            out[m.group(1)] = re.sub(r"^jit\([^)]*\)/", "", o.group(1))[:120]
    return out


def scope_ops(hlo_text: str, scope: str) -> set:
    """Names of the instructions of an optimised HLO module that do work
    of ``scope``: those whose own metadata names it, and fusions of which
    some fused instruction's does. A loop or a conditional is not counted
    for its body, whose ops the trace shows on their own."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line) if cur is not None else None
        if m:
            cur.append((m.group(1), m.group(2)))
    # under AD the scope reads jvp(<scope>) and transpose(jvp(<scope>))
    tag = re.compile(r'op_name="[^"]*' + re.escape(scope) + r'[/")]')
    scoped = {c for c, ins in comps.items()
              if any(tag.search(text) for _, text in ins)}
    out = set()
    for ins in comps.values():
        for name, text in ins:
            called = _CALLS.search(text)
            if tag.search(text) or (" fusion(" in text and called
                                    and called.group(1) in scoped):
                out.add(name)
    return out


# ------------------------------------------------------------- reduce
class Trace:
    """Reductions over the flat events of one traced window."""

    def __init__(self, events: list):
        self.events = events
        wins = [e for e in events if e["name"] == WINDOW]
        if not wins:
            raise ValueError(f"no {WINDOW!r} annotation in the trace")
        w = max(wins, key=lambda e: e["d"])
        self.lo, self.hi = w["t"], w["t"] + w["d"]
        self.devices = sorted({e["pl"] for e in events
                               if e["pl"].startswith("/device:")
                               and e["ln"] == OPS_LINE})

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def ops(self, device: str) -> list:
        return [e for e in self.events
                if e["pl"] == device and e["ln"] == OPS_LINE]

    def _iv(self, evs) -> list:
        return union(clip([(e["t"], e["t"] + e["d"]) for e in evs],
                          self.lo, self.hi))

    def busy_s(self) -> float:
        """Seconds in which some op ran on a device, averaged over the
        devices."""
        if not self.devices:
            return 0.0
        return sum(length(self._iv(self.ops(d))) for d in self.devices) \
            * 1e-9 / len(self.devices)

    def scoped_s(self, names, module: str) -> float:
        """Device seconds of the ops named in ``names`` (instruction names,
        see :func:`scope_ops`) that ran inside an execution of the program
        ``module``, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for d in self.devices:
            runs = union((e["t"], e["t"] + e["d"]) for e in self.events
                         if e["pl"] == d and e["ln"] == MODULES_LINE
                         and e["name"].split("(")[0] == module)
            evs = [e for e in self.ops(d) if instruction(e["name"]) in names]
            tot += length(self._iv(_inside(evs, runs)))
        return tot * 1e-9 / len(self.devices)

    def exposed_collective_s(self) -> float:
        """Collective op time during which no other op runs on that
        device, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for d in self.devices:
            evs = self.ops(d)
            coll = [e for e in evs if COLLECTIVE.search(e["name"])]
            comp = [e for e in evs if not COLLECTIVE.search(e["name"])]
            tot += length(subtract(self._iv(coll), self._iv(comp)))
        return tot * 1e-9 / len(self.devices)

    def top_ops(self, k: int = 10, labels=None) -> list:
        """[[op, device seconds]] of the ops that took most time on the
        first device, loops and conditionals left out for their bodies.
        An op is named by its instruction and, where ``labels``
        (:func:`op_names`) knows it, the JAX op it came from."""
        if not self.devices:
            return []
        tot = {}
        for e in self.ops(self.devices[0]):
            s, t = max(e["t"], self.lo), min(e["t"] + e["d"], self.hi)
            if t <= s:
                continue
            name = instruction(e["name"])
            if _CONTAINER.match(name):
                continue
            key = (f"{name} {labels[name]}" if labels and name in labels
                   else e["name"][:160])
            tot[key] = tot.get(key, 0.0) + (t - s) * 1e-9
        return [[n, v] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """[[what the host was doing, seconds]] for the longest spans of
        the window in which no op ran on the first device; the host's
        activity is the innermost harness annotation over the gap's
        middle, else "host outside the harness's phases"."""
        if not self.devices:
            return []
        busy = self._iv(self.ops(self.devices[0]))
        gaps = subtract([[self.lo, self.hi]], busy)
        host = [e for e in self.events if e["name"].startswith(HOST_PREFIX)
                and e["name"] != WINDOW]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            mid = (s + e) / 2
            over = [h for h in host if h["t"] <= mid <= h["t"] + h["d"]]
            label = (min(over, key=lambda h: h["d"])["name"] if over
                     else "host outside the harness's phases")
            out.append([label, (e - s) * 1e-9])
        return out

    def breakdown(self, labels=None) -> dict:
        return {"device_ops": self.top_ops(labels=labels),
                "idle_gaps": self.idle_gaps()}


class Capture:
    """Profile a sub-window: ``start()`` starts JAX's profiler and opens
    the window annotation, ``stop()`` closes both, and ``finish()``, once
    the run is over, reads the trace back (which keeps the host busy for
    a while) and removes the profile files. Only the process that holds
    the chip can trace it."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self._ann = None
        self.stopped = False
        self.trace = None

    def start(self):
        import jax
        os.makedirs(self.logdir, exist_ok=True)
        jax.profiler.start_trace(self.logdir)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()

    @property
    def active(self) -> bool:
        return self._ann is not None

    def stop(self):
        import jax
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()
        self.stopped = True

    def finish(self):
        import shutil
        try:
            if self._ann is not None:
                self.stop()
            if self.stopped and self.trace is None:
                self.trace = Trace(flatten(newest_xplane(self.logdir)))
        finally:
            shutil.rmtree(self.logdir, ignore_errors=True)


class Phase:
    """A host annotation (``bench.<name>``) that only costs anything
    while a capture is active."""

    def __init__(self, cap):
        self.cap = cap

    def __call__(self, name: str):
        import contextlib
        if self.cap is None or not self.cap.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(HOST_PREFIX + name)
