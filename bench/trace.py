"""From a profiler trace of the window to device times.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the reduction needs, as plain lists (the same shape as the excerpt
under ``bench/testdata``): per device plane the events of its ``XLA Ops``
line (the HLO instruction's text, start ns, duration ns) and, from the
host, the benchmark's own ``bench.*`` spans.

On the TPU an op event carries no name-stack metadata: its name is the
HLO instruction's text, and loop and conditional ops are short events of
their own, not spans around their bodies.  So ``op_names`` reads the
name stack of every instruction from the compiled programs' HLO text,
keyed by the instruction's name and result type, and
``category`` sorts by it:

* ``local``: inside the round program's local-step scan, a name stack
  holding ``while/body`` (the batch sampling and the model's forward,
  backward and update);
* ``comm``: inside the comm step's ``lax.cond`` branch, a stack holding
  ``cond/branch_1_fun``;
* ``other``: everything else (the cohort gather and scatter, the trace
  updates, other programs, and the copies XLA inserts with no name
  stack, such as layout changes of the state between the comm
  workspace and the model's leaves).

Of the two scopes, the one further out in the stack decides, wherever it
sits, so a scope wrapped around either keeps the rule.  An op whose text
is in no program's text is ``unnamed``; it counts as other, and
``Summary.check`` refuses a window where unnamed ops take more than
``UNNAMED_SHARE`` of busy time, or where the local or the comm step read
no time: the rule then no longer sees the program.

``summarize`` reduces them over the ``bench.window`` span:

* busy time is the union of the device's op intervals, and each instant
  of it belongs to the innermost op running then, so local + comm +
  other = busy;
* collective time is the innermost time of all-reduce, all-gather,
  reduce-scatter and collective-permute ops;
* idle gaps (window time no op covers) are named by the innermost
  ``bench.*`` host span running at their midpoint.

Times are averaged over the devices traced.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
KEY_CHARS = 160  # how much of an op's text a trace event keeps
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
INSTR = re.compile(r"^\s*(?:ROOT )?(%\S+ = .*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
KEY = 120  # a key shorter than the text kept, so a cut result type keys alike
SCOPES = {("while", "body"): "local", ("cond", "branch_1_fun"): "comm"}
UNNAMED_SHARE = 0.01


def load(trace_dir: str) -> dict:
    """The events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host, cpu = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.append({"name": plane.name, "events": [
                    [ev.name[:KEY_CHARS], float(ev.start_ns),
                     float(ev.duration_ns)] for ev in line.events]})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
                    elif "hlo_op" in dict(ev.stats):
                        cpu.append([ev.name[:KEY_CHARS], float(ev.start_ns),
                                    float(ev.duration_ns)])
    if not devices and cpu:
        # a CPU rehearsal: the host threads' XLA ops stand for one device
        devices = [{"name": "/host:CPU", "events": cpu}]
    return {"devices": devices, "host": host}


def key(text: str) -> str:
    """An instruction's key: its name and result type (``%fusion.7 =
    f32[8]{0}``), which the trace and the program text print alike; the
    operands they print differently."""
    name, _, rest = text.partition(" = ")
    end = len(rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end = i + 1
                break
    else:
        end = rest.find(" ") if " " in rest else end
    return (name + " = " + rest[:end])[:KEY]


def op_names(hlo_texts) -> Dict[str, str]:
    """Instruction key -> JAX name stack, from compiled programs'
    ``as_text()``."""
    out: Dict[str, str] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = INSTR.match(line)
            if m is not None:
                name = OP_NAME.search(line)
                out[key(m.group(1))] = name.group(1) if name else ""
    return out


def category(op_name: str) -> str:
    parts = op_name.split("/")
    for pair in zip(parts, parts[1:]):
        if pair in SCOPES:
            return SCOPES[pair]
    return "other"


def innermost(events: List[list], lo: float, hi: float):
    """Split ``[lo, hi]`` into the stretches each event is innermost in:
    yields ``(event index or -1 for idle, start, end)``.  Of the events
    open at an instant, the innermost is the one that started last."""
    bounds = []
    for i, ev in enumerate(events):
        s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
        if e > s:
            bounds.append((s, 1, i))
            bounds.append((e, 0, i))
    bounds.sort()
    heap: List[Tuple[float, int]] = []  # (-start, index) of open events
    ended = set()
    t = lo
    for when, kind, i in bounds:
        while heap and heap[0][1] in ended:
            heapq.heappop(heap)
        if when > t:
            yield (heap[0][1] if heap else -1), t, when
            t = when
        if kind:
            heapq.heappush(heap, (-max(events[i][1], lo), i))
        else:
            ended.add(i)
    if hi > t:
        yield -1, t, hi


@dataclass
class Summary:
    window_s: float
    busy_s: float
    cat_s: Dict[str, float]
    collective_s: float
    remainder_s: float
    unnamed_s: float = 0.0  # busy time of ops in no program's text
    ops: List[Tuple[str, float]] = field(default_factory=list)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def check(self) -> None:
        """Refuse a window the attribution rule did not see: no local or
        no comm time, or unnamed ops over ``UNNAMED_SHARE`` of busy."""
        for c in ("local", "comm"):
            if self.cat_s[c] <= 0:
                raise ValueError(f"no device time attributed to {c}: the "
                                 "name stacks no longer match bench/trace.py")
        if self.unnamed_s > UNNAMED_SHARE * self.busy_s:
            raise ValueError(
                f"ops in no program's text took {self.unnamed_s:.6f} s of "
                f"{self.busy_s:.6f} s busy")

    def breakdown(self) -> dict:
        return {"device_ops": [[n[:100], v] for n, v in self.ops[:10]],
                "idle_gaps": [list(x) for x in self.gaps[:10]]}


def _host_span(host: List[list], t: float, default: str = "none") -> str:
    best, best_start = default, None
    for name, s, d in host:
        if s <= t <= s + d and name != "bench.window" and (
                best_start is None or s > best_start):
            best, best_start = name, s
    return best


def summarize(tr: dict, names: Dict[str, str], chips: int = 1) -> Summary:
    """Device times over the host's ``bench.window`` span, averaged over
    the devices traced (``chips`` of them are expected); ``names`` is
    ``op_names`` of the programs that ran."""
    win = [h for h in tr["host"] if h[0] == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    devices = tr["devices"]
    if len(devices) != chips:
        raise ValueError(f"trace has {len(devices)} device planes, "
                         f"expected {chips}")
    busy, coll = 0.0, 0.0
    cat: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    unnamed = 0.0
    cat_of: Dict[str, str] = {}  # an op's text -> its category, once
    for dev in devices:
        evs = dev["events"]
        for i, s, e in innermost(evs, lo, hi):
            d = (e - s) * 1e-9
            if i < 0:
                gaps[_host_span(tr["host"], 0.5 * (s + e))] += d
                continue
            name = evs[i][0]
            if name not in cat_of:
                stack = names.get(key(name))
                cat_of[name] = "unnamed" if stack is None else category(stack)
            busy += d
            if cat_of[name] == "unnamed":
                unnamed += d
                cat["other"] += d
            else:
                cat[cat_of[name]] += d
            ops[name] += d
            if COLLECTIVE.search(name):
                coll += d
    k = 1.0 / len(devices)
    cat_s = {c: cat.get(c, 0.0) * k for c in ("local", "comm", "other")}
    busy *= k
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy, cat_s=cat_s,
        collective_s=coll * k, remainder_s=busy - sum(cat_s.values()),
        unnamed_s=unnamed * k,
        ops=sorted(((n, v * k) for n, v in ops.items()),
                   key=lambda x: -x[1]),
        gaps=sorted(((n, v * k) for n, v in gaps.items()),
                    key=lambda x: -x[1]))
