"""The program's own spans and counters reach the benchmark's readers.

A tiny Engine + Scheduler serves a few requests under the profiler, in
this process: ``trace.load``'s compact form carries every span the program
wrote through ``repro.tracing.span`` under ``program``, with its stats,
and leaves ``host`` as it was. The span names come from the program's
source, and the engine's counters reach ``Step.counts``; a compact trace
without ``program`` still loads, and the readers of the program's spans
and counters read None where those are missing.
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from sartbench import trace  # noqa: E402
from sartbench.loadgen import engine_counters  # noqa: E402
from sartbench.spec import load_reader  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / \
    "qwen2-0.5b.reason-poisson.3steps.json"
# the spans the program writes today (docs/serving.md, "Tracing a live
# server"); a later one adds to them
PROGRAM_SPANS = {"engine.step", "engine.prepare", "engine.dispatch",
                 "engine.sync", "engine.harvest", "prm.head", "sched.fill",
                 "sched.admit", "sched.step_tail", "sched.prune",
                 "sched.finalize"}


def test_span_names_are_read_from_the_programs_source():
    assert trace.program_span_names() >= PROGRAM_SPANS


def test_span_names_of_each_form_of_call():
    import ast
    src = '''
from ..tracing import span
from repro.tracing import span as mark
from repro import tracing
import repro.tracing
import repro.tracing as tr
with span("a.one", rows=1): pass
with mark("a.two"): pass
tracing.span("a.three")
repro.tracing.span("a.four")
tr.span("a.five")
span(name)                      # not a literal: not found
other.span("b.one")             # not the program's span
'''
    assert sorted(trace._span_names(ast.parse(src))) == [
        "a.five", "a.four", "a.one", "a.three", "a.two"]
    # without an import of the tracing module, no call is its span
    assert list(trace._span_names(ast.parse('span("c.one")'))) == []


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from repro.core import RewardHeadPRM, Scheduler, SchedulerConfig
    from repro.core.prm import init_prm_head
    from repro.models import Model, ModelConfig
    from repro.serving import Engine, EngineConfig, SamplingParams
    from sartbench.proxy import EngineProxy, profiler_span

    model = Model(ModelConfig(name="traced", arch_type="dense",
                              num_layers=1, d_model=32, vocab_size=64,
                              num_heads=2, num_kv_heads=1, d_ff=64))
    params = model.init_params(jax.random.PRNGKey(0))
    eng = Engine(model, params, EngineConfig(
        page_size=8, num_pages=128, max_slots=6, max_pages_per_branch=8,
        eos_id=64, prefill_chunk=16,
        sampling=SamplingParams(temperature=1.0, top_p=1.0)),
        prm_params=init_prm_head(jax.random.PRNGKey(1), 32))
    proxy = EngineProxy(eng, eos_id=64)
    proxy.span = profiler_span
    sch = Scheduler(proxy, RewardHeadPRM(proxy), SchedulerConfig(
        policy="sart", n=3, m=2, window=4, max_tokens=10), answer_fn=len)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 60, size=k).tolist() for k in (7, 19, 11)]
    for i, p in enumerate(prompts):
        req = sch.submit(p, arrival=i)
        proxy.lengths[req.request_id] = [99] * 3   # never cut short
    out = tmp_path_factory.mktemp("served")
    jax.profiler.start_trace(str(out))
    try:
        sch.run(max_steps=500)
    finally:
        jax.profiler.stop_trace()
    assert all(r.done for r in sch.requests.values())
    path = sorted(out.glob("**/*.xplane.pb"))[-1]
    names = trace.program_span_names()
    # every host event, with the stats of the program's spans
    events = sorted(([ev.name, int(ev.start_ns), int(ev.duration_ns),
                      dict(ev.stats) if ev.name in names else {}]
                     for plane in ProfileData.from_file(str(path)).planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines for ev in line.events),
                    key=lambda e: e[1])
    return SimpleNamespace(compact=trace.load(str(out)), events=events,
                           names=names, engine=eng, prompts=prompts)


def test_compact_trace_carries_the_programs_spans_with_stats(served):
    c, eng = served.compact, served.engine
    want = [e for e in served.events if e[0] in served.names]
    assert [list(e) for e in c["program"]] == want
    assert {e[0] for e in c["program"]} >= PROGRAM_SPANS - {"sched.prune"}
    prep = [e[3] for e in c["program"] if e[0] == "engine.prepare"]
    assert all(set(s) >= {"rows", "lanes", "bucket", "cows"} for s in prep)
    assert sum(s["rows"] for s in prep) == eng.generated_tokens
    admits = {e[3]["request_id"]: e[3]["prompt_tokens"]
              for e in c["program"] if e[0] == "sched.admit"}
    assert admits == {i: len(p) for i, p in enumerate(served.prompts)}
    # the benchmark's own spans are as they were, and apart
    assert [list(e) for e in c["host"]] == [
        e[:3] for e in served.events if e[0] in trace.HOST_SPANS]
    # the compact form survives its file
    assert json.loads(json.dumps(c))["program"] == \
        [list(e[:3]) + [e[3]] for e in c["program"]]


def test_engine_host_busy_by_hand_on_a_served_trace(served):
    c = served.compact
    lo, hi = trace.window(c)
    steps = [e for e in c["program"] if e[0] == "engine.step"]
    busy = sum(e[2] for e in c["program"] if e[0] in (
        "engine.prepare", "engine.dispatch", "engine.harvest"))
    got = load_reader("engine_host_busy_ms_per_step").read(
        SimpleNamespace(trace=c, trace_ns=(lo, hi)))
    assert got == busy / len(steps) / 1e6
    mean_step = sum(e[2] for e in steps) / len(steps) / 1e6
    assert 0 < got < mean_step


def test_a_trace_without_program_spans_loads_and_reads_none():
    compact = json.loads(FIXTURE.read_text())
    bare = {k: v for k, v in compact.items() if k != "program"}
    lo, hi = trace.window(bare)
    assert trace.program(bare, lo, hi) is None
    assert trace.program(compact, lo, hi) is not None
    reader = load_reader("engine_host_busy_ms_per_step")
    assert reader.read(SimpleNamespace(trace=bare, trace_ns=(lo, hi))) \
        is None
    assert reader.read(SimpleNamespace(trace=None, trace_ns=None)) is None
    # the recorded chip trace: three steps' engine.prepare, dispatch and
    # harvest, worked by hand
    assert reader.read(SimpleNamespace(trace=compact, trace_ns=(lo, hi))) \
        == (598160 + 4976490 + 138030 + 596000 + 4972759 + 150510
            + 648070 + 5557279 + 152320) / 3 / 1e6


def test_engine_counters_by_name():
    # where the engine gives counters(), those are its counters
    stub = SimpleNamespace(counters=lambda: {"moe.experts_reached": 3},
                           rows_computed=5)
    assert engine_counters(stub) == {"moe.experts_reached": 3}
    # otherwise every public attribute that holds an integer
    stub = SimpleNamespace(rows_computed=5, probe=np.int64(4), _seen=3,
                           ready=True, share=0.5, name="x", slots=[1])
    assert engine_counters(stub) == {"rows_computed": 5, "probe": 4}
    assert type(engine_counters(stub)["probe"]) is int


def test_padded_row_share_reads_none_without_its_counters():
    from sartbench.loadgen import Records, Step
    reader = load_reader("padded_row_share")
    step = Step(1.0, 1.1, 3, 30, [], 3, 5, None,
                {"rows_computed": 8, "rows_useful": 6})
    rec = Records(t_origin=0.0, t_open=0.5, t_close=2.0, steps=[step],
                  itl=[], tick_time={}, due={}, late=[])
    run = SimpleNamespace(rec=rec)
    # the engine had no such counters
    assert reader.read(run) is None
    rec.counters_close = {"rows_computed": 8, "rows_useful": 6}
    assert reader.read(run) == 25.0
    # no step in the window
    rec.t_open = 1.5
    assert reader.read(run) is None
