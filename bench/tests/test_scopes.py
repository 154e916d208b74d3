"""The window read by the program's own names: inclusive scope times,
unscoped time, the parity with the rule's categories, and idle gaps named
by the program's host spans."""

import pytest

from bench import scopes, trace

LOCAL = "jit(round)/tamuna.local_step/while/body/dot_general"
UPLINK = ("jit(round)/cond/branch_1_fun/tamuna.comm_step/tamuna.uplink/"
          "pallas_call")
PACK = "jit(round)/cond/branch_1_fun/tamuna.comm_step/concatenate"
HLO = """HloModule jit_round
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="LOCAL"}
  %custom-call.2 = f32[8]{0} custom-call(), metadata={op_name="UPLINK"}
  %concatenate.3 = f32[16]{0} concatenate(f32[8]{0} %a, f32[8]{0} %b), metadata={op_name="PACK"}
  %copy.4 = f32[8]{0} copy(f32[8]{0} %q)
}""".replace("LOCAL", LOCAL).replace("UPLINK", UPLINK).replace("PACK", PACK)
NAMES = trace.op_names([HLO])
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
CALL = "%custom-call.2 = f32[8]{0} custom-call()"
CONCAT = "%concatenate.3 = f32[16]{0} concatenate(f32[8]{0} %a)"
COPY = "%copy.4 = f32[8]{0} copy(f32[8]{0} %q)"


def read(events, host=(), spans=()):
    tr = {"devices": [{"name": "/device:TPU:0", "events": events}],
          "host": [["bench.window", 0.0, 100.0], *host]}
    s = trace.summarize(tr, NAMES)
    return s, scopes.reduce(tr, NAMES, list(spans), s.cat_s)


def test_nested_scopes_are_inclusive():
    s, r = read([[FUSION, 0, 20], [CONCAT, 30, 10], [CALL, 40, 30]])
    assert r["scope_s"] == pytest.approx({
        "tamuna.local_step": 20e-9,
        "tamuna.comm_step": 40e-9,  # the pack and the kernel inside it
        "tamuna.uplink": 30e-9,
    })
    assert r["parity"] == pytest.approx({"local": 1.0, "comm": 1.0})
    assert r["unscoped_s"] == 0.0


def test_unscoped_holds_ops_with_no_scope_and_unnamed_ops():
    s, r = read([[FUSION, 0, 20], [COPY, 20, 5],
                 ["%fusion.99 = f32[8]{0} fusion()", 30, 2],
                 [CALL, 40, 10]])
    assert r["unscoped_s"] == pytest.approx(7e-9)
    assert s.unnamed_s == pytest.approx(2e-9)
    # the innermost op decides: a copy running inside a scoped op's
    # interval takes that stretch out of the scope
    s, r = read([[FUSION, 0, 20], [COPY, 5, 5], [CALL, 40, 10]])
    assert r["scope_s"]["tamuna.local_step"] == pytest.approx(15e-9)
    assert r["unscoped_s"] == pytest.approx(5e-9)
    assert r["parity"]["local"] == pytest.approx(1.0)


def test_idle_gaps_take_the_innermost_program_span():
    host = [["bench.rounds", 0.0, 85.0], ["bench.drain", 85.0, 15.0]]
    spans = [["tamuna.round", 0.0, 25.0], ["tamuna.drain", 25.0, 10.0],
             ["tamuna.flush", 35.0, 3.0], ["tamuna.round", 38.0, 17.0],
             ["tamuna.init_carry", 58.0, 4.0],
             ["tamuna.round", 200.0, 5.0]]  # outside the window
    s, r = read([[FUSION, 0, 20], [CALL, 40, 10], [FUSION, 70, 10]],
                host, spans)
    # idle 20-40, 50-70 and 80-100, each named at its midpoint
    assert dict(r["gaps"]) == pytest.approx({
        "tamuna.drain": 20e-9, "tamuna.init_carry": 20e-9,
        "bench.drain": 20e-9})
    # the benchmark's own rule names the first two by bench.rounds
    assert dict(s.gaps) == pytest.approx({"bench.rounds": 40e-9,
                                          "bench.drain": 20e-9})
    assert r["span_counts"] == {"tamuna.drain": 1, "tamuna.flush": 1,
                                "tamuna.init_carry": 1, "tamuna.round": 2}
