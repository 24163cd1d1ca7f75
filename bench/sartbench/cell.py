"""One run of one cell: set-up, warm stretch, window, metrics, check.

Set-up draws the weights, builds ``Engine`` and ``Scheduler`` (SART, the
reward-head PRM) from the configuration file, compiles the step programs
the traffic uses by calling each once, and runs the cell's own traffic for
``warm_s`` seconds; the window follows without a pause. Everything from
process start to the window's opening is ``setup_s``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import shutil
import time
from typing import Callable, List, Optional

import numpy as np

from . import check, stats, trace as tracemod
from .loadgen import LoadGen, Records, request_times
from .proxy import EngineProxy, profiler_span
from .spec import BENCH, ROOT, Cell, Work
from .traffic import Traffic
from .weights import make_weights

OUT = BENCH / "out"     # traces of --trace 1 runs (compact form)
TRACE_S = 4.0          # profiled stretch of a --trace 1 window, seconds
CHECK_REQUESTS = 3     # requests in the sample the reference checks


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise NoChip(f"JAX found no device ({exc})") from exc
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, handed to the program through the variable it reads."""
    path = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    from repro.launch.serve import setup_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Run:
    """What a metric's reader reads: the window's records (each step's
    shapes and the engine's counters that moved in it, ``Step.counts``),
    the requests' host times, and with ``--trace 1`` the compact trace
    (device operations, the benchmark's spans, the program's spans and
    their stats under ``program``)."""
    cell: Cell
    device_kind: str
    rec: Records
    requests: List[dict]
    setup_s: float
    trace: Optional[dict] = None          # compact trace, --trace 1 only
    trace_ns: Optional[tuple] = None      # traced stretch in trace time

    @property
    def work(self) -> Work:
        """What each layer holds, by kind (``spec.describe``)."""
        return self.cell.work

    @property
    def page_size(self) -> int:
        return self.cell.config["engine"]["page_size"]

    @property
    def num_pages(self) -> int:
        return self.cell.config["engine"]["num_pages"]

    def traced_steps(self):
        """Steps recorded while the profiler ran."""
        if self.rec.trace_span is None:
            return []
        a, b = self.rec.trace_span
        return [s for s in self.rec.steps if a <= s.t0 and s.t1 <= b]


def block_table_pages(cell: Cell) -> int:
    """The engine's block-table width: pages for the mix's longest prompt
    plus its longest branch, so the table covers every request and no
    more."""
    tr = cell.traffic
    tokens = tr["prompt_tokens"]["max"] + tr["branch_tokens"]["max"]
    return -(-tokens // cell.config["engine"]["page_size"])


def build(cell: Cell, seed: int):
    """(model, params, prm head, engine) for the cell's configuration."""
    import jax.numpy as jnp
    from repro.models import Model, ModelConfig
    from repro.serving import Engine, EngineConfig, SamplingParams
    conf = cell.config
    model = Model(ModelConfig(**conf["model"]), dtype=jnp.bfloat16)
    params, prm = make_weights(model, seed)
    e = dict(conf["engine"])
    sampling = SamplingParams(temperature=e.pop("temperature"),
                              top_p=e.pop("top_p", 1.0))
    engine = Engine(model, params, EngineConfig(
        sampling=sampling, seed=int(seed) % (2 ** 31),
        max_pages_per_branch=block_table_pages(cell), **e),
        prm_params=prm)
    return model, params, prm, engine


def warm_variants(engine) -> List[str]:
    """Call the step program once in each shape it can take, on zeros,
    discarding the result, and the small operations the engine applies to
    each shape's outputs (slicing the slots' hidden rows, taking a chunk's
    last logits, sampling from them, the reward head): this compiles (or
    loads from the persistent cache) all of it before any request
    arrives, so nothing compiles in the window."""
    import jax
    import jax.numpy as jnp
    from repro.serving.sampling import sample
    slots = engine.cfg.max_slots
    names = []
    for v in engine.step_variants():
        args = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), v.args)
        _, hidden, logits, _ = engine._step_jit(
            engine.params, engine.state, *args, lane_buckets=v.lane_buckets)
        jax.block_until_ready(hidden[:slots])
        if v.lane_buckets:
            row = logits[slots]
            jax.block_until_ready(sample(engine._next_rng(), row,
                                         engine.cfg.sampling))
        names.append(v.name)
    engine.score_slots()
    return names


class CompileClock:
    """Host times at which XLA compiled something (backend compiles)."""

    def __init__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.times if lo <= t <= hi)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, device_check: bool = True,
             fault: Optional[Callable] = None, control: bool = False,
             log=sys.stderr) -> dict:
    """One run; returns the result object the last line prints. With
    ``control`` the numbers held to the limits are the control's (the
    reference at fp8 in the program's place, on the same sample), and the
    program's stand beside them under ``"program_check"``."""
    import jax
    if device_check:
        devices = require_tpu(cell.chips)
    else:
        devices = jax.devices()[:cell.chips]
    dev = devices[0]
    setup_compile_cache()
    from repro.core import RewardHeadPRM, Scheduler, SchedulerConfig

    conf, spec = cell.config, cell.traffic
    model, params, prm, engine = build(cell, seed)
    if fault is not None:
        fault(engine)
    proxy = EngineProxy(engine, conf["engine"]["eos_id"])
    tr = Traffic(spec, seed, conf["model"]["vocab_size"],
                 conf["engine"]["max_slots"])
    sch = Scheduler(proxy, RewardHeadPRM(proxy), SchedulerConfig(
        policy="sart", n=tr.n, m=tr.m,
        max_tokens=int(spec["branch_tokens"]["max"]) + 1,
        **conf.get("scheduler", {})), answer_fn=lambda tokens: len(tokens))
    variants = warm_variants(engine)
    print(f"warmed step variants: {variants}", file=log, flush=True)
    compiles = CompileClock()

    trace_dir = None
    kw = {}
    if trace:
        trace_dir = OUT / "traces" / f"{cell.name}.{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo = max((seconds - TRACE_S) / 2, 0.0)
        kw = dict(trace=(lo, min(lo + TRACE_S, seconds)),
                  start_trace=lambda: jax.profiler.start_trace(
                      str(trace_dir)),
                  stop_trace=jax.profiler.stop_trace, record_tables=True,
                  span=profiler_span)
        proxy.span = profiler_span
    loadgen = LoadGen(sch, proxy, tr, **kw)

    rec = loadgen.run(float(spec["warm_s"]), seconds)
    setup_s = rec.t_open - t_start

    run = Run(cell, dev.device_kind, rec, request_times(rec, sch), setup_s)
    if trace and rec.trace_span is not None:
        run.trace = tracemod.load(str(trace_dir))
        run.trace_ns = tracemod.window(run.trace)
        tracemod.save(run.trace, OUT / f"{cell.name}.{seed}.trace.json")
        shutil.rmtree(trace_dir, ignore_errors=True)
    faults = sch.metrics()["faults"]
    quarantined = faults["quarantined_requests"]
    attempted = len(rec.due)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics_of(kind):
        value = m.reader.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    _log_counts(run, compiles.between(rec.t_open, rec.t_close), faults,
                log)

    # the check runs on the window's output once the program's state is
    # freed, so that the reference's memory does not set the peak
    branches = check.served_branches(sch, proxy, conf["engine"]["eos_id"])
    finished = [r["request_id"] for r in run.requests
                if r["finish"] is not None and rec.in_window(r["finish"])]
    picked = check.sample(branches, finished, seed, CHECK_REQUESTS)
    engine.state.clear()
    engine._last_hidden = None
    del sch, loadgen, proxy, engine
    gc.collect()
    t_check = time.perf_counter()
    sampled = [b for r in picked for b in branches[r]]
    numbers = check.compare(conf, params, prm, sampled) if picked else None
    program = None
    if control and picked:
        program = numbers
        numbers = check.compare(conf, params, prm, sampled, control=True)
    print(f"check: {len(picked)} requests "
          f"({sum(r in finished for r in picked)} finished in the window), "
          f"{numbers['tokens_compared'] if numbers else 0} tokens, "
          f"{numbers['rewards_compared'] if numbers else 0} rewards, "
          f"{time.perf_counter() - t_check:.1f} s", file=log, flush=True)
    limits = conf["check"]

    def against_limits(nums):
        return {name: {"value": nums[name], "limit": limit}
                for name, limit in limits.items()}
    compared = against_limits(numbers) if numbers is not None else {}
    correct = (numbers is not None and numbers["tokens_compared"] > 0
               and numbers["rewards_compared"] > 0
               and all(c["value"] <= c["limit"] for c in compared.values()))
    hidden_faults = {k: faults[k] for k in
                     ("step_faults", "engine_restarts", "quarantined")
                     if faults[k]}
    correct &= not hidden_faults
    if not picked:
        print("check: no branch was served", file=log)
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=log, flush=True)

    result = {"correct": bool(correct), "attempted": attempted,
              "failed": quarantined, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": mem}}
    if trace and run.trace is not None:
        lo, hi = run.trace_ns
        result["device"]["busy_s"] = tracemod.busy_ns(run.trace, lo, hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": tracemod.top_ops(run.trace, lo, hi),
            "idle_gaps": tracemod.idle_by_host(run.trace, lo, hi)}
    result["load"] = load_summary(run)
    if program is not None:
        result["program_check"] = against_limits(program)
    result["check"] = compared
    return result


def load_summary(run: Run) -> dict:
    """Requests sent, answered in the window, and outstanding (due and
    not finished) when the window opened and when it closed: a queue that
    grows through the window means load above what the system sustains.
    ``busy_slots``: decode rows per window step, on average; under the
    engine's slots, the load left the batch part empty."""
    rec = run.rec
    steps = stats.window_steps(run)

    def outstanding(t):
        return sum(1 for r in run.requests if r["due"] <= t
                   and (r["finish"] is None or r["finish"] > t))
    return {"sent": len(rec.due),
            "answered": sum(1 for r in run.requests if r["finish"]
                            is not None and rec.in_window(r["finish"])),
            "outstanding_open": outstanding(rec.t_open),
            "outstanding_close": outstanding(rec.t_close),
            "busy_slots": (sum(s.rows for s in steps) / len(steps)
                           if steps else 0.0)}


def _log_counts(run: Run, compiles: int, faults: dict, log) -> None:
    rec = run.rec
    late = np.asarray(rec.late) if rec.late else np.zeros(1)
    steps = stats.window_steps(run)
    done = sum(1 for r in run.requests
               if r["finish"] is not None and rec.in_window(r["finish"]))
    print(f"window: {rec.t_close - rec.t_open:.3f} s, {len(steps)} steps, "
          f"{sum(s.tokens for s in steps)} tokens, {len(rec.itl)} "
          f"token gaps, {done} answers, {compiles} compiles", file=log,
          flush=True)
    print(f"generator: {len(rec.due)} requests sent, lateness p50 "
          f"{np.median(late) * 1e3:.1f} ms, max {late.max() * 1e3:.1f} ms",
          file=log, flush=True)
    print(f"faults: {json.dumps(faults)}", file=log, flush=True)
