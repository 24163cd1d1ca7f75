"""A whole run at smoke widths on the CPU: the proxy drives Scheduler and
Engine through a short window; the counts, the load summary and the
tick-to-time map hold; the engine's counters reach each step's record; a
span and a counter the program adds reach reader files of their own; the
check passes the sound program and fails each fault the serving cells can
have; the measuring path refuses the CPU."""
import io
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from sartbench import cell as cellmod  # noqa: E402
from sartbench.spec import (Cell, Metric, load_config,  # noqa: E402
                            load_reader, load_traffic)

E2E = ("setup_s", "itl_ms_p95", "tokens_per_s")
SEED = 2 ** 31 + 4242


@pytest.fixture(autouse=True, scope="module")
def no_persistent_cache():
    """The runs here compile at smoke widths without the harness's
    persistent cache, which would otherwise stay on (in the checkout) for
    the rest of the test process."""
    saved = cellmod.setup_compile_cache
    cellmod.setup_compile_cache = lambda: None
    yield
    cellmod.setup_compile_cache = saved


def smoke_cell(traffic="reason-poisson") -> Cell:
    """The qwen2-0.5b configuration at smoke widths, with the mix's shape
    kept and its sizes cut to what a CPU runs in seconds."""
    conf = load_config("qwen2-0.5b")
    conf["model"].update(num_layers=2, d_model=128, num_heads=4,
                         num_kv_heads=2, head_dim=32, d_ff=256,
                         vocab_size=512)
    conf["engine"].update(num_pages=256, max_slots=8, eos_id=512)
    # limits of their own at these widths, from readings on the CPU over
    # nine seeds: the program at most 0.046 and 0.0041, the fp8 control at
    # least 0.175 and 0.020
    conf["check"] = {"token_gap": 0.12, "reward_gap": 0.008}
    tr = load_traffic(traffic)
    tr.update(prompt_tokens={"median": 40, "sigma": 0.5, "min": 16,
                             "max": 150},
              branch_tokens={"median": 40, "sigma": 0.6, "min": 8,
                             "max": 200},
              warm_s=1.0, n=4, m=2, cycle=8)
    if tr["loop"] == "open":
        tr["rate_per_s"] = 4.0
    metrics = [Metric(n, "x", "lower", "end_to_end", None, load_reader(n))
               for n in E2E]
    return Cell(f"smoke.{traffic}", 1, conf, tr, metrics)


def run(cell, fault=None, seconds=2.0):
    log = io.StringIO()
    res = cellmod.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                           device_check=False, fault=fault, log=log)
    return res, log.getvalue()


@pytest.fixture(scope="module")
def sound():
    """A sound run, with the window's records and the scheduler kept."""
    kept = {}
    orig = cellmod.request_times

    def keep(rec, sch):
        kept["rec"], kept["sch"] = rec, sch
        return orig(rec, sch)

    cellmod.request_times = keep
    try:
        res, log = run(smoke_cell())
    finally:
        cellmod.request_times = orig
    return res, log, kept["rec"], kept["sch"]


def test_sound_run_is_correct_and_counted(sound):
    res, log, _, _ = sound
    assert res["correct"], log
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert set(res["check"]) == {"token_gap", "reward_gap"}
    for c in res["check"].values():
        assert c["value"] <= c["limit"]
    assert "window:" in log and "generator:" in log and "check" in log
    assert " 0 compiles" in log
    assert " 0 rewards," not in log


def test_window_records_and_samples(sound):
    """The records the metrics read, from the sound run's window."""
    _, _, rec, sch = sound
    cell = smoke_cell()
    steps = [s for s in rec.steps if rec.in_window(s.t1)]
    assert steps and all(s.t0 < s.t1 for s in rec.steps)
    # every window step's tokens are its gaps
    assert sum(s.tokens for s in steps) == len(rec.itl)
    # tick -> time is monotone and each tick maps to a step's end
    ticks = sorted(rec.tick_time)
    times = [rec.tick_time[t] for t in ticks]
    assert times == sorted(times)
    ends = {s.t1 for s in rec.steps}
    assert all(rec.tick_time[t] in ends for t in ticks if t > 0)
    # scheduler stamps map to host times in order
    for req in sch.requests.values():
        if req.done:
            assert rec.host_time(req.first_service) <= rec.host_time(
                req.finish)
    # every request sent has its due time, and the load summary counts
    # the requests outstanding at the close among them
    from sartbench.loadgen import request_times
    run_ = cellmod.Run(cell, "cpu", rec, request_times(rec, sch), 0.0)
    assert {r["request_id"] for r in run_.requests} == set(rec.due)
    load = cellmod.load_summary(run_)
    assert load["sent"] == len(rec.due)
    assert load["outstanding_close"] == sum(
        1 for r in run_.requests if r["due"] <= rec.t_close
        and (r["finish"] is None or r["finish"] > rec.t_close))
    assert 0 < load["busy_slots"] <= cell.config["engine"]["max_slots"]


def test_counter_changes_sum_to_the_window(sound):
    """Each step keeps what moved in the engine's counters; over the
    window's steps the changes sum to the counters' change from the
    window's open to its close."""
    _, _, rec, _ = sound
    steps = [s for s in rec.steps if rec.in_window(s.t1)]
    assert {"rows_computed", "rows_useful", "attn_blocks_run",
            "attn_blocks_grid"} <= set(rec.counters_close)
    for name, close in rec.counters_close.items():
        assert sum(s.counts.get(name, 0) for s in steps) == \
            close - rec.counters_open.get(name, 0), name
    # every step runs its slots' rows, and every key kept moved
    assert all(s.counts["rows_computed"] > 0 for s in rec.steps)
    assert all(v for s in rec.steps for v in s.counts.values())
    run_ = SimpleNamespace(rec=rec)
    share = load_reader("padded_row_share").read(run_)
    computed = sum(s.counts["rows_computed"] for s in steps)
    useful = sum(s.counts.get("rows_useful", 0) for s in steps)
    assert share == 100.0 * (1.0 - useful / computed)
    assert 0 < share < 100


PROBE_MODULE = """
from repro.tracing import span


def mark(n):
    with span("probe.span", n=n):
        pass
"""
# readers a later PR would add as files of their own
PROBE_READERS = {
    "probe_span_n": """
from sartbench import trace


def read(run):
    if run.trace is None:
        return None
    spans = trace.program(run.trace, *run.trace_ns)
    if spans is None:
        return None
    return float(sum(e[3]["n"] for e in spans if e[0] == "probe.span"))
""",
    "probe_count": """
from sartbench.stats import counter_sum


def read(run):
    n = counter_sum(run, run.traced_steps(), "probe_count")
    return None if n is None else float(n)
""",
}


def test_a_new_span_and_counter_reach_new_reader_files(tmp_path,
                                                       monkeypatch):
    """A module the program adds writes a span through
    ``repro.tracing.span``, and the engine gains a counter; two reader
    files in a new ``bench/metrics/`` read them from a traced run, with
    no edit to the harness."""
    import importlib
    from sartbench import trace
    src = tmp_path / "src" / "repro"
    src.mkdir(parents=True)
    (src / "probe_ext.py").write_text(PROBE_MODULE)
    metrics = tmp_path / "bench" / "metrics"
    metrics.mkdir(parents=True)
    for name, text in PROBE_READERS.items():
        (metrics / f"{name}.py").write_text(text)
    monkeypatch.syspath_prepend(str(tmp_path / "src"))
    monkeypatch.setattr(cellmod, "OUT", tmp_path / "out")
    probe = importlib.import_module("repro.probe_ext")
    try:
        assert "probe.span" in trace.program_span_names()

        def fault(engine):
            engine.probe_count = 0
            step = engine.decode_step

            def decode_step():
                out = step()
                engine.probe_count += 1
                probe.mark(2)
                return out
            engine.decode_step = decode_step

        base = smoke_cell()
        cell = Cell(base.name, 1, base.config, base.traffic, [
            Metric(n, "x", "higher", "per_layer", None,
                   load_reader(n, tmp_path / "bench"))
            for n in PROBE_READERS])
        res = cellmod.run_cell(cell, SEED, 2.0, True, time.perf_counter(),
                               device_check=False, fault=fault,
                               log=io.StringIO())
    finally:
        sys.modules.pop("repro.probe_ext", None)
    assert res["correct"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    compact = json.loads((tmp_path / "out" /
                          f"{cell.name}.{SEED}.trace.json").read_text())
    steps = [e for e in compact["host"] if e[0] == "engine.decode_step"]
    # one probe span of n = 2 and one count in each traced step
    assert got == {"probe_count": float(len(steps)),
                   "probe_span_n": 2.0 * len(steps)}
    assert len(steps) > 0


def test_closed_loop_keeps_its_backlog():
    res, log = run(smoke_cell("reason-backlog"))
    assert res["correct"], log
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def _alter_tokens(engine):
    """A token altered where it is produced."""
    step = engine.decode_step

    def decode_step():
        out = step()
        for slot in out:
            tok = (out[slot] + 1) % engine.model.cfg.vocab_size
            engine.slots[slot].tokens[-1] = tok
            engine._tokens[slot] = tok
            out[slot] = tok
        return out
    engine.decode_step = decode_step


def _state_unchanged(engine):
    """A step that returns its state unchanged."""
    jitted = engine._step_jit

    def step(params, state, *args, **kw):
        tokens, hidden, logits, _ = jitted(params, state, *args, **kw)
        return tokens, hidden, logits, dict(state)
    engine._step_jit = step


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_check_fails_a_broken_timed_path(fault):
    res, log = run(smoke_cell(), fault=fault)
    assert not res["correct"], log
    assert res["check"]["token_gap"]["value"] > \
        res["check"]["token_gap"]["limit"]


def test_measuring_path_refuses_the_cpu(capsys):
    import run as entry
    assert entry.main(["--workload", "qwen2-0.5b.reason-poisson", "--seed",
                       "1", "--seconds", "1", "--trace", "0"]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_control_fails_where_the_program_passes():
    """The control, the reference at fp8 in the program's place on the
    same sample, goes through the same limits and comes out as not
    correct, while the program's numbers on that sample pass them (at
    smoke widths, one seed; the chip readings that set the limits are in
    PERF.md)."""
    log = io.StringIO()
    res = cellmod.run_cell(smoke_cell(), SEED, 2.0, False,
                           time.perf_counter(), device_check=False,
                           control=True, log=log)
    assert res["correct"] is False, log.getvalue()
    assert list(res)[-2:] == ["program_check", "check"]
    program, control = res["program_check"], res["check"]
    for c in program.values():
        assert c["value"] <= c["limit"], (program, control)
    assert any(c["value"] > c["limit"] for c in control.values()), control


def test_sample_holds_a_rewarded_branch():
    """The reward head is checked on finished branches: where the seed's
    draw holds none, a request that has one takes the draw's last place."""
    from sartbench.check import Branch, sample

    def req(rid, n, reward=None):
        return [Branch(rid, [1, 2], [3] * n, reward)]
    by_request = {0: req(0, 50), 1: req(1, 9), 2: req(2, 8), 3: req(3, 7),
                  4: req(4, 5, reward=0.5)}
    for seed in range(20):
        picked = sample(by_request, [0, 1, 2, 3], seed, 3)
        assert len(picked) == 3 and picked[0] == 0
        assert 4 in picked
    # none rewarded: the draw stands as it is
    del by_request[4]
    assert len(sample(by_request, [0, 1, 2, 3], 5, 3)) == 3
