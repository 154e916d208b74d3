#!/usr/bin/env python3
"""A traced window read by the program's own names (``repro.spans``).

``bench/trace.py`` sorts device time by JAX's own name-stack components
(``while/body``, ``cond/branch_1_fun``) and names idle gaps by the
benchmark's ``bench.*`` host spans.  ``reduce`` reads the same window by
the names the round engine puts on its work:

* ``scope_s``: the inclusive device time of each ``tamuna.*`` scope, an
  instant of busy time counting for every ``tamuna.*`` component of the
  stack of the innermost op running then;
* ``unscoped_s``: busy time of ops whose stack holds no ``tamuna.*``
  component, ops in no program's text included;
* ``parity``: ``tamuna.local_step`` over the rule's local time and
  ``tamuna.comm_step`` over its comm time;
* ``gaps``: idle time named by the innermost ``bench.*`` or
  ``tamuna.*`` host span at the gap's midpoint (``bench/trace.py``'s
  rule, with the program's spans added);
* ``span_counts``: how many of each ``tamuna.*`` host span the window
  holds.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

(``--rehearse`` as ``bench/run.py`` takes it) runs the cell as
``bench/run.py --trace 1`` does, in-process, and prints
one more JSON line after the result line: ``reduce``'s numbers, and
``lower_s``, the seconds of set-up JAX spent tracing and lowering
(``jaxpr_trace_duration`` + ``jaxpr_to_mlir_module_duration``; the
benchmark's ``compile_s`` adds the backend compile or cache load).  A
program without ``repro/spans.py`` reads no scope and no span.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from collections import Counter, defaultdict
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# ``repro/spans.py``'s names, written out: an older checkout lacks it
PREFIX = "tamuna."
LOCAL, COMM = "tamuna.local_step", "tamuna.comm_step"
LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")


def host_spans(trace_dir: str) -> List[list]:
    """``[name, start ns, duration ns]`` of the ``tamuna.*`` host events
    of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend([ev.name, float(ev.start_ns),
                            float(ev.duration_ns)] for ev in line.events
                           if ev.name.startswith(PREFIX))
    return out


def reduce(tr: dict, names: Dict[str, str], spans: List[list],
           cat_s: Dict[str, float]) -> dict:
    """The window of ``tr`` (``bench.trace.load``'s events) by the
    program's names: ``names`` is ``bench.trace.op_names`` of the programs
    that ran, ``spans`` the ``tamuna.*`` host spans (``host_spans``),
    ``cat_s`` the rule's times (``bench.trace.Summary.cat_s``)."""
    from bench import trace

    win = next(h for h in tr["host"] if h[0] == "bench.window")
    lo, hi = win[1], win[1] + win[2]
    host = tr["host"] + spans
    scope: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    unscoped = 0.0
    scopes_of: Dict[str, List[str]] = {}  # an op's text -> its scopes
    for dev in tr["devices"]:
        evs = dev["events"]
        for i, s, e in trace.innermost(evs, lo, hi):
            d = (e - s) * 1e-9
            if i < 0:
                gaps[trace._host_span(host, 0.5 * (s + e))] += d
                continue
            name = evs[i][0]
            if name not in scopes_of:
                stack = names.get(trace.key(name)) or ""
                scopes_of[name] = sorted({p for p in stack.split("/")
                                          if p.startswith(PREFIX)})
            for sc in scopes_of[name]:
                scope[sc] += d
            if not scopes_of[name]:
                unscoped += d
    k = 1.0 / len(tr["devices"])
    scope_s = {sc: v * k for sc, v in sorted(scope.items())}
    parity = {cat: (scope_s.get(sc, 0.0) / cat_s[cat] if cat_s[cat] else None)
              for cat, sc in (("local", LOCAL), ("comm", COMM))}
    in_window = Counter(n for n, s, d in spans if s >= lo and s + d <= hi)
    return {"scope_s": scope_s, "unscoped_s": unscoped * k,
            "parity": parity,
            "gaps": sorted(([n, v * k] for n, v in gaps.items()),
                           key=lambda x: -x[1]),
            "span_counts": dict(sorted(in_window.items()))}


def main(argv=None) -> int:
    """``bench/run.py --trace 1`` with the reads of ``reduce`` and
    ``lower_s`` added around the harness's own functions."""
    argv = list(sys.argv[1:] if argv is None else argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, run, trace

    lower = {"all": 0.0}
    got: dict = {}
    init, first_rounds = harness.Cell.__init__, harness.Cell.first_rounds
    load, summarize = trace.load, trace.summarize

    def on_duration(event, secs, **_):
        if event in LOWER_EVENTS:
            lower["all"] += secs

    def init_read(cell, *args, **kw):
        # jax is imported by now, after ``run.main`` set its environment
        import jax

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        init(cell, *args, **kw)

    def first_rounds_read(cell, seed):
        out = first_rounds(cell, seed)
        got["lower_s"] = lower["all"]
        return out

    def load_read(trace_dir):
        got["spans"] = host_spans(trace_dir)
        return load(trace_dir)

    def summarize_read(tr, names, chips=1):
        s = summarize(tr, names, chips)
        got.update(reduce(tr, names, got.pop("spans", []), s.cat_s))
        return s

    harness.Cell.__init__ = init_read
    harness.Cell.first_rounds = first_rounds_read
    trace.load, trace.summarize = load_read, summarize_read
    rc = run.main(argv + ["--trace", "1"])
    print(json.dumps(got), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
