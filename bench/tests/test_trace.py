"""The trace reduction: busy time as a union, the local / comm / other
attribution, idle gaps named by host spans, and the peaks table."""

import gzip
import json
import os

import pytest

from bench import harness, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
LOCAL = "jit(<unknown>)/while/body/jvp(loss)/dot_general"
COMM = "jit(<unknown>)/cond/branch_1_fun/shard_map/pallas_call"
HLO = """HloModule jit__unknown
ENTRY %main {
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="LOCAL"}
  ROOT %custom-call.3 = f32[8]{0} custom-call(), metadata={op_name="COMM"}
  %copy.4 = f32[8]{0} copy(f32[8]{0} %q), metadata={op_name="jit(<unknown>)/gather"}
  %while.1 = (s32[]) while((s32[]) %t), body=%b, metadata={op_name="jit(<unknown>)/while"}
}""".replace("LOCAL", LOCAL).replace("COMM", COMM)
NAMES = trace.op_names([HLO])
FUSION = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
CALL = "%custom-call.3 = f32[8]{0} custom-call()"
COPY = "%copy.4 = f32[8]{0} copy(f32[8]{0} %q)"
LOOP = "%while.1 = (s32[]) while((s32[]) %t), body=%b"


def tr(events, host=None):
    host = host or [["bench.window", 0.0, 100.0]]
    return {"devices": [{"name": "/device:TPU:0", "events": events}],
            "host": host}


def test_op_names_key_instructions_by_name_and_result_type():
    assert NAMES[trace.key(FUSION)] == LOCAL
    assert NAMES[trace.key(CALL)] == COMM
    # the trace prints operands with their shapes; the key leaves them out
    assert trace.key("%fusion.2 = f32[8]{0} fusion(f32[8]{0:T(128)} %p)") \
        == trace.key(FUSION) == "%fusion.2 = f32[8]{0}"
    assert trace.key("%t.1 = (f32[2]{0}, s32[]) tuple(%a, %b)") == \
        "%t.1 = (f32[2]{0}, s32[])"
    assert trace.category(NAMES[trace.key(FUSION)]) == "local"
    assert trace.category(NAMES[trace.key(CALL)]) == "comm"
    assert trace.category(NAMES[trace.key(COPY)]) == "other"
    assert trace.category("") == "other"


@pytest.mark.parametrize("stack,cat", [
    ("jit(<unknown>)/while/body/jvp(loss)/dot_general", "local"),
    ("jit(<unknown>)/local_steps/while/body/dot_general", "local"),
    ("jit(<unknown>)/jit(round)/cond/branch_1_fun/pallas_call", "comm"),
    # the outer scope decides: a cond inside the scan is the local step's
    ("jit(<unknown>)/while/body/cond/branch_1_fun/add", "local"),
    ("jit(<unknown>)/cond/branch_1_fun/while/body/add", "comm"),
    ("jit(<unknown>)/while", "other"),
    ("jit(<unknown>)/cond/branch_0_fun/copy", "other"),
])
def test_scopes_match_anywhere_in_the_stack(stack, cat):
    assert trace.category(stack) == cat


def test_check_refuses_what_the_rule_does_not_see():
    # local and comm seen, every op named: accepted
    trace.summarize(tr([[FUSION, 0, 20], [CALL, 30, 10]]), NAMES).check()
    # no comm time: the comm scope no longer matches
    with pytest.raises(ValueError, match="comm"):
        trace.summarize(tr([[FUSION, 0, 20], [COPY, 30, 10]]), NAMES).check()
    # an op in no program's text over 1% of busy time
    s = trace.summarize(tr([[FUSION, 0, 20], [CALL, 30, 10],
                            ["%fusion.99 = f32[8]{0} fusion()", 50, 1]]),
                        NAMES)
    assert s.unnamed_s == pytest.approx(1e-9)
    assert s.cat_s["other"] == pytest.approx(1e-9)
    with pytest.raises(ValueError, match="no program"):
        s.check()


def test_busy_is_the_union_of_op_intervals():
    # two overlapping ops and one nested inside the first
    s = trace.summarize(tr([["a", 10, 30], ["b", 30, 20], ["c", 15, 5]]),
                        NAMES)
    assert s.busy_s == pytest.approx(40e-9)
    assert s.window_s == pytest.approx(100e-9)


def test_innermost_op_takes_the_time_and_categories_sum_to_busy():
    # a loop op enclosing a local-step op, then a comm op and a copy
    evs = [[LOOP, 0, 50], [FUSION, 10, 20], [CALL, 60, 10], [COPY, 80, 5]]
    s = trace.summarize(tr(evs), NAMES)
    assert s.cat_s["local"] == pytest.approx(20e-9)
    assert s.cat_s["comm"] == pytest.approx(10e-9)
    assert s.cat_s["other"] == pytest.approx(35e-9)  # while 30 + copy 5
    assert s.remainder_s == pytest.approx(0.0, abs=1e-18)
    assert sum(s.cat_s.values()) == pytest.approx(s.busy_s)


def test_window_clips_events_and_gaps_take_the_host_span():
    host = [["bench.window", 100.0, 100.0], ["bench.rounds", 100.0, 60.0],
            ["bench.drain", 160.0, 40.0]]
    evs = [[FUSION, 50, 90], [CALL, 170, 10]]
    s = trace.summarize(tr(evs, host), NAMES)
    assert s.busy_s == pytest.approx(50e-9)  # 40 of x inside, 10 of y
    gaps = dict(s.gaps)
    assert gaps["bench.rounds"] == pytest.approx(30e-9)  # 140..170
    assert gaps["bench.drain"] == pytest.approx(20e-9)  # 180..200


def test_collectives_are_counted_by_op_name():
    s = trace.summarize(tr([["%all-reduce.7 = f32[8]{0} all-reduce()", 0, 8],
                            [CALL, 10, 4]]), NAMES)
    assert s.collective_s == pytest.approx(8e-9)


def test_devices_are_averaged_and_counted():
    t = tr([[FUSION, 0, 10]])
    t["devices"].append({"name": "/device:TPU:1",
                         "events": [[FUSION, 0, 30]]})
    assert trace.summarize(t, NAMES, chips=2).busy_s == pytest.approx(20e-9)
    with pytest.raises(ValueError):
        trace.summarize(t, NAMES, chips=1)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary", rehearse=False)
    assert harness.peaks_for("TPU v5 lite", rehearse=False)[
        "hbm_bytes_per_s"] == 819e9


def test_real_trace_excerpt():
    """120 ms of a chip trace of `stablelm_3b_share.stacked_sync_rounds`
    around a comm step (TPU v5 lite, 62,737 op events), with the name
    stacks of its ops from the compiled round program."""
    with gzip.open(os.path.join(DATA, "trace_excerpt.json.gz"), "rt") as f:
        ex = json.load(f)
    s = trace.summarize(ex, ex["names"])
    assert 0 < s.busy_s <= s.window_s
    for c in ("local", "comm", "other"):
        assert s.cat_s[c] > 0.01  # seconds: all three parts of a round
    assert sum(s.cat_s.values()) == pytest.approx(s.busy_s)
    # the round program's ops are named through its text; the few left
    # are ``run_rounds``'s own eager ops (key splits, trace buffers)
    ops = [e[0] for d in ex["devices"] for e in d["events"]]
    unnamed = [o for o in ops if trace.key(o) not in ex["names"]]
    assert len(unnamed) < 1e-3 * len(ops)
    s.check()
