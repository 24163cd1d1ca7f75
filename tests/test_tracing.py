"""The program's spans and row counters (``repro.tracing``).

A tiny Engine + Scheduler (SART, the reward-head PRM, the benchmark's
proxy with its own spans) serves a few requests to the end under the
profiler, in this process; the trace is read back through
``jax.profiler.ProfileData`` and through the benchmark's ``trace.load``.
A compact trace recorded on a v5e chip checks the same structure on the
chip's clock: three steps of the ``qwen2-0.5b.reason-poisson`` cell, in
``trace.load``'s form plus the program's spans under ``"program"``, with
the device operations no other operation encloses and the attention
kernels, named by their HLO instruction.
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from sartbench import trace  # noqa: E402

# every span the program writes (docs/serving.md, "Tracing a live server")
PROGRAM_SPANS = ("engine.step", "engine.prepare", "engine.dispatch",
                 "engine.sync", "engine.harvest", "prm.head", "sched.fill",
                 "sched.admit", "sched.step_tail", "sched.prune",
                 "sched.finalize")
PHASES = ("engine.prepare", "engine.dispatch", "engine.sync",
          "engine.harvest")
CHIP_TRACE = BENCH / "tests" / "data" / "qwen2-0.5b.reason-poisson.3steps.json"


def host_events(trace_dir: str):
    """[name, start, duration, stats] of every host event, by start (the
    stats of the program's spans only)."""
    path = sorted(Path(trace_dir).glob("**/*.xplane.pb"))[-1]
    out = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
            dict(ev.stats) if ev.name in PROGRAM_SPANS else {}]
           for plane in ProfileData.from_file(str(path)).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events]
    return sorted(out, key=lambda e: e[1])


def within(child, parent) -> bool:
    return parent[1] <= child[1] and \
        child[1] + child[2] <= parent[1] + parent[2]


def check_steps(program):
    """Each engine.step holds its four phases, in order, one after
    another, filling at least 95% of it."""
    steps = [e for e in program if e[0] == "engine.step"]
    for step in steps:
        inside = [e for e in program if e[0] in PHASES and within(e, step)]
        assert [e[0] for e in inside] == list(PHASES)
        for a, b in zip(inside, inside[1:]):
            assert a[1] + a[2] <= b[1]
        assert sum(e[2] for e in inside) >= 0.95 * step[2]
    return steps


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
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 60, size=k).tolist() for k in (5, 21, 13)]
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
    events = host_events(str(out))
    return SimpleNamespace(
        dir=str(out), events=events, engine=eng, prompts=prompts,
        program=[e for e in events if e[0] in PROGRAM_SPANS])


def test_engine_step_holds_its_four_phases_in_order(served):
    steps = check_steps(served.program)
    assert len(steps) == served.engine.decode_steps_executed > 0


def test_prepare_stats_match_the_engines_counts(served):
    eng = served.engine
    prep = [e[3] for e in served.program if e[0] == "engine.prepare"]
    # a step's rows are its active slots, each of which samples a token
    assert sum(s["rows"] for s in prep) == eng.generated_tokens
    assert sum(s["lanes"] for s in prep) == eng.prefill_chunk_steps > 0
    assert sum(eng.cfg.max_slots + s["lanes"] * s["bucket"]
               for s in prep) == eng.rows_computed
    # useful rows: every token sampled, and every prompt row once
    assert eng.rows_useful == eng.generated_tokens + sum(
        map(len, served.prompts))
    assert eng.rows_useful < eng.rows_computed


def test_scheduler_span_stats(served):
    prog = served.program
    fills = [e[3] for e in prog if e[0] == "sched.fill"]
    assert sum(s["admitted"] for s in fills) == 3
    assert sum(s["spawned"] for s in fills) == 9      # n = 3 per request
    admits = {e[3]["request_id"]: e[3]["prompt_tokens"]
              for e in prog if e[0] == "sched.admit"}
    assert admits == {i: len(p) for i, p in enumerate(served.prompts)}
    finals = [e[3] for e in prog if e[0] == "sched.finalize"]
    assert sorted(s["request_id"] for s in finals) == [0, 1, 2]
    assert all(s["completed"] >= 2 for s in finals)   # early stop at m = 2
    # every pruning round's reward-head calls sit inside it
    for rnd in (e for e in prog if e[0] == "sched.prune"):
        heads = [e for e in prog if e[0] == "prm.head" and within(e, rnd)]
        assert len(heads) == rnd[3]["scored"]
    assert {e[3]["slots"] for e in prog if e[0] == "prm.head"} == {6}


def test_every_program_span_is_listed_and_emitted(served):
    emitted = {e[0] for e in served.events
               if e[0].split(".")[0] in ("engine", "sched", "prm")}
    assert emitted - set(trace.HOST_SPANS) == set(PROGRAM_SPANS)


def test_benchmark_reduction_keeps_only_its_own_spans(served):
    compact = trace.load(served.dir)
    host = compact["host"]
    bench = [tuple(e[:3]) for e in served.events
             if e[0] in trace.HOST_SPANS]
    assert [tuple(e) for e in host] == bench
    assert {e[0] for e in host} == {"engine.decode_step", "prm.score"}
    # one engine.step inside each of the proxy's engine.decode_step spans
    steps = [e for e in served.program if e[0] == "engine.step"]
    outer = [e for e in host if e[0] == "engine.decode_step"]
    assert len(outer) == len(steps)
    assert all(within(s, o) for s, o in zip(steps, outer))


# ------------------------------------------------ a trace from the chip


@pytest.fixture(scope="module")
def chip():
    compact = json.loads(CHIP_TRACE.read_text())
    return SimpleNamespace(compact=compact, window=trace.window(compact))


def test_chip_trace_steps_hold_their_phases(chip):
    steps = check_steps(chip.compact["program"])
    assert len(steps) == 3
    assert [e[0] for e in chip.compact["host"]].count(
        "engine.decode_step") == 3


def test_chip_trace_idle_falls_in_program_spans(chip):
    """With the program's spans beside the benchmark's, each idle gap of
    the chip goes to the innermost of either list: most of the idle time
    lies in the program's spans, where the benchmark's spans alone put it
    in engine.decode_step."""
    lo, hi = chip.window
    c = chip.compact
    alone = dict(trace.idle_by_host(c, lo, hi))
    both = dict(trace.idle_by_host(
        dict(c, host=c["host"] + [e[:3] for e in c["program"]]), lo, hi))
    assert sum(alone.values()) == pytest.approx(sum(both.values()))
    assert not set(alone) & set(PROGRAM_SPANS)
    program = sum(v for k, v in both.items() if k in PROGRAM_SPANS)
    assert program >= 0.8 * sum(both.values())


def test_chip_trace_reduction(chip):
    """The benchmark's reduction on the chip's own trace, against sums
    taken by hand: busy time is the outermost operations' time, the
    decode kernel's time is its operations' time, and the engine's host
    time as the benchmark reads it from outside (decode-step span less
    device time) is at least what the engine's own phases other than
    the sync took."""
    from sartbench.spec import load_reader
    lo, hi = chip.window
    c = chip.compact
    ops = sorted(c["devices"][0], key=lambda e: e[1])
    outer, end = [], -1
    for e in ops:
        if e[1] >= end:
            outer.append(e)
            end = e[1] + e[2]
    assert trace.busy_ns(c, lo, hi) == sum(e[2] for e in outer)
    assert trace.kernel_ns(c, ("paged_attention",), lo, hi) == sum(
        e[2] for e in ops if "paged_attention" in e[0]) > 0
    steps = [e for e in c["program"] if e[0] == "engine.step"]
    busy_host = sum(e[2] for e in c["program"] if e[0] in (
        "engine.prepare", "engine.dispatch", "engine.harvest"))
    outside = load_reader("engine_host_ms_per_step").read(
        SimpleNamespace(trace=c, trace_ns=chip.window))
    assert outside * 1e6 * len(steps) >= busy_host > 0
