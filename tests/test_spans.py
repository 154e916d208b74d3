"""The program's own names on its work (``repro.spans``): the device scopes
in the compiled round programs and the host spans ``run_rounds`` emits
under a profiler.  Tiny sizes, one CPU device, in-process."""

import collections
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import spans
from repro.data import DataConfig, device_sampler
from repro.data.pipeline import SyntheticTokenPipeline
from repro.dist import comm_ws, rounds, tamuna_dp
from repro.launch.mesh import make_host_mesh
from repro.models.transformer import ModelConfig

N = 4
OP_NAME = re.compile(r'op_name="([^"]*)"')
RANDOM_LOOP = re.compile(r"/jit\(_(shuffle|threefry_\w+)\)(/|$)")


@pytest.fixture(scope="module")
def setup():
    mesh = make_host_mesh(1, 1)
    cfg = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=1, d_ff=64, vocab=64, dtype=jnp.float32,
                      remat=False)
    dcfg = DataConfig(seq_len=8, per_client_batch=1, vocab=64, seed=0,
                      n_clients=N)
    pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
    return mesh, cfg, pipe.device_data(), device_sampler(dcfg, cfg, mesh)


def init_state(cfg, mesh):
    # the state does not depend on the uplink or the cohort
    return tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tamuna_cfg(),
                                n=N)


@pytest.fixture(scope="module")
def new_state(setup):
    """A fresh state from one compiled program (an eager op per leaf
    would compile each)."""
    mesh, cfg, _, _ = setup
    specs = tamuna_dp.state_pspecs(
        jax.eval_shape(lambda: init_state(cfg, mesh)), cfg, mesh)
    return jax.jit(lambda: init_state(cfg, mesh), out_shardings=jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P)))


def stacks(round_fn):
    """The name stacks of the instructions of the programs ``round_fn``
    has run (their executables exist, so nothing compiles again)."""
    return [name for low in round_fn.lowered().values()
            for name in OP_NAME.findall(low.compile().as_text())]


def holds(stack, scope):
    return scope in stack.split("/")


def rule(stack):
    """``bench/trace.py``'s attribution: the outer of the local-step scan
    body and the comm ``cond`` branch in the stack decides."""
    parts = stack.split("/")
    for pair in zip(parts, parts[1:]):
        if pair == ("while", "body"):
            return "local"
        if pair == ("cond", "branch_1_fun"):
            return "comm"
    return "other"


class _Lengths:
    """A round-length source that deals the given lengths."""

    def __init__(self, lengths):
        self.lengths = list(lengths)

    def geometric(self, p):
        return self.lengths.pop(0)


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts the spans
    opened, without a profiler."""

    def __init__(self, counts):
        self.counts = counts

    def __call__(self, name, **_):
        self.counts[name] += 1
        return contextlib.nullcontext()


@contextlib.contextmanager
def recorded_spans():
    counts = collections.Counter()
    real = jax.profiler.TraceAnnotation
    jax.profiler.TraceAnnotation = _Recorder(counts)
    try:
        yield counts
    finally:
        jax.profiler.TraceAnnotation = real


def tamuna_cfg(uplink="masked_psum", c=2):
    return tamuna_dp.DistTamunaConfig(gamma=0.05, c=c, s=2, p=0.5,
                                      uplink=uplink)


def make_engine(setup, tcfg, pipelined=False):
    mesh, cfg, _, sampler = setup
    make = (rounds.make_pipelined_round_fn if pipelined
            else rounds.make_round_fn)
    return make(cfg, tcfg, mesh, sample_batch=sampler, max_L=1, n=N)


def drive(setup, state, engine, lengths, flush, **kw):
    """``run_rounds`` (with ``staleness``, ``run_rounds_pipelined``) over
    ``lengths``."""
    mesh, _, data, _ = setup
    run = (rounds.run_rounds_pipelined if "staleness" in kw
           else rounds.run_rounds)
    with jax.set_mesh(mesh):
        state, _ = run(state, round_fn=engine, data=data,
                       key=jax.random.key(3), rounds=len(lengths),
                       rng=_Lengths(lengths), p=0.5, flush_every=flush,
                       **kw)
        jax.block_until_ready(state)


@pytest.fixture(scope="module")
def elastic(setup, new_state):
    """The cyclic elastic engine, its program compiled by a first run of
    three rounds under the span recorder, and the spans it counted."""
    engine = make_engine(setup, tamuna_cfg())
    with recorded_spans() as counts:
        drive(setup, new_state(), engine, [1, 1, 1], flush=2)
    return engine, counts


# (uplink, cohort, fault-tolerant): the elastic cyclic body, the all-rows
# block_rs body, and the fault-tolerant comm branch
PROGRAMS = [("masked_psum", 2, False), ("block_rs", N, False),
            ("masked_psum", 2, True)]


@pytest.fixture(scope="module", params=PROGRAMS,
                ids=["cyclic-elastic", "block_rs-all-rows", "faults"])
def program(request, setup, new_state, elastic):
    """A one-step round program of each body, compiled by one run (the
    elastic engine's by its fixture's)."""
    mesh, _, data, _ = setup
    uplink, c, faulted = request.param
    with jax.set_mesh(mesh):  # as the programs ran
        if request.param == PROGRAMS[0]:
            return request.param, elastic[0], stacks(elastic[0])
        round_fn = make_engine(setup, tamuna_cfg(uplink, c))
        kw = {}
        if faulted:
            cohort = np.arange(c, dtype=np.int32)
            member = np.isin(np.arange(N), cohort)
            kw = dict(cohort=jnp.asarray(cohort), down=jnp.asarray(member),
                      arrived=jnp.asarray(member), correct=True)
        carry = rounds.init_carry(new_state(), jax.random.key(1), 1,
                                  robust_n=N if faulted else 0)
        jax.block_until_ready(round_fn(carry, data, 1, 0, **kw))
        return request.param, round_fn, stacks(round_fn)


def test_rule_scopes_lie_inside_the_program_scopes(program):
    """Every op the benchmark's rule counts as the local step (a stack
    holding ``while/body``) or as the comm step (``cond/branch_1_fun``)
    carries the program's own scope for it.  The exceptions are the
    elastic chunk's cohort draw and its keys: loops of ``jax.random``'s
    own (the shuffle; threefry, rolled on the CPU), which the rule files
    as local and the scopes leave out of the local step."""
    _, _, names = program
    local = [s for s in names if rule(s) == "local"]
    comm = [s for s in names if rule(s) == "comm"]
    assert local and comm
    stray = [s for s in local if not holds(s, spans.LOCAL_STEP)]
    assert all(RANDOM_LOOP.search(s.split("/while/body")[0])
               for s in stray)
    assert all(holds(s, spans.COMM_STEP) for s in comm)
    # the trace writes are named apart from both
    assert any(holds(s, spans.TRACES) for s in names)


def test_uplink_and_cohort_scopes(program):
    (uplink, c, _), round_fn, names = program
    # the aggregation lies inside the comm step
    up = [s for s in names if holds(s, spans.UPLINK)]
    assert up and all(holds(s, spans.COMM_STEP) for s in up)
    for scope in (spans.COHORT_GATHER, spans.COHORT_SCATTER):
        assert any(holds(s, scope) for s in names) == round_fn.elastic
    assert round_fn.elastic == (c < N)


@pytest.mark.parametrize("template", ["cyclic", "blocked"])
@pytest.mark.parametrize("impl", ["ws", "dense", "pallas"])
def test_every_aggregation_path_holds_the_uplink_scope(template, impl):
    """Each comm impl puts its aggregate (for ``pallas`` the kernels) in
    ``tamuna.uplink``; the DownCom broadcast of ``x_bar`` stays out."""
    c, s, d = 3, 2, 1024
    x = {"w": jnp.arange(N * d, dtype=jnp.float32).reshape(N, d) / d}
    h = {"w": jnp.zeros((N, d), jnp.float32)}
    slot = jnp.asarray([0, 1, 2, -1], jnp.int32)

    def comm(x, h):
        if template == "cyclic":
            return comm_ws.cyclic_comm(x, h, slot, c, s, 0.5, impl=impl)
        return comm_ws.blocked_comm(x, h, 0, N, s, 0.5, impl=impl, c=c,
                                    slot_of=slot)

    text = jax.jit(comm).lower(x, h).compile().as_text()
    names = OP_NAME.findall(text)
    assert any(holds(n, spans.UPLINK) for n in names)
    assert not all(holds(n, spans.UPLINK) for n in names)


def host_events(trace_dir):
    """``[name, start ns, end ns]`` of the host events of the one
    ``jax.profiler`` trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    return [[ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def span_counts(events):
    return collections.Counter(n for n, _, _ in events
                               if n.startswith(spans.PREFIX))


def test_synchronous_rounds_spans_under_a_profiler(setup, new_state,
                                                   elastic, tmp_path):
    """A CPU profiler trace of ``run_rounds``: one ``tamuna.round`` a
    round, one ``tamuna.drain`` and ``tamuna.flush`` a flush, one fresh
    carry; the programs compiled before the trace, so no compile span."""
    engine, _ = elastic
    state = new_state()
    jax.profiler.start_trace(str(tmp_path))
    try:
        drive(setup, state, engine, [1] * 5, flush=2)
    finally:
        jax.profiler.stop_trace()
    assert span_counts(host_events(tmp_path)) == {
        spans.INIT_CARRY: 1, spans.ROUND: 5, spans.DRAIN: 3, spans.FLUSH: 3}


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["synchronous", "pipelined"])
def test_round_loops_open_their_spans(setup, new_state, elastic, pipelined):
    """Each round loop opens one ``tamuna.round`` a round (a pipeline step),
    one ``tamuna.drain`` and
    ``tamuna.flush`` a flush and one fresh carry; the synchronous
    engine's first call of a program opens ``tamuna.compile.<bucket>``."""
    want = {spans.INIT_CARRY: 1, spans.DRAIN: 2, spans.FLUSH: 2}
    if pipelined:
        # the all-rows body (two programs) at staleness 0
        engine = make_engine(setup, tamuna_cfg(c=N), pipelined=True)
        with recorded_spans() as counts:
            drive(setup, new_state(), engine, [1, 1, 1], flush=2,
                  staleness=0)
        want[spans.ROUND] = 3
    else:
        counts = elastic[1]  # three rounds, the program's first call
        want.update({spans.ROUND: 3, f"{spans.COMPILE}1": 1})
    assert dict(counts) == want
