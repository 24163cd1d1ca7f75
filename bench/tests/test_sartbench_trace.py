"""The reduction from a trace to the per-layer numbers, on a hand-made
trace in the compact form ``trace.load`` produces, with answers worked by
hand."""
import sys
from pathlib import Path


sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from sartbench import trace  # noqa: E402

# one chip; times in ns
HAND = {
    "devices": [[["fusion.1", 0, 10], ["paged_attention.3", 5, 10],
                 ["fusion.2", 30, 10]]],
    "host": [["engine.decode_step", 0, 20], ["scheduler", 20, 5],
             ["prm.score", 21, 2], ["engine.decode_step", 25, 20]],
}


def test_window_is_first_to_last_step():
    assert trace.window(HAND) == (0, 45)


def test_busy_and_idle_by_hand():
    # union of [0,10) [5,15) [30,40) is 25 ns of 45
    assert trace.busy_ns(HAND, 0, 45) == 25
    assert trace.idle_gaps(HAND["devices"][0], 0, 45) == [(15, 15),
                                                           (40, 5)]
    # gap [15,30) has its midpoint (22) in prm.score, inside scheduler;
    # gap [40,45) in the second decode step
    got = dict(trace.idle_by_host(HAND, 0, 45))
    assert got == {"prm.score": 15e-9, "engine.decode_step": 5e-9}


def test_kernel_time_and_clipping_by_hand():
    assert trace.kernel_ns(HAND, ("paged_attention",), 0, 45) == 10
    assert trace.kernel_ns(HAND, ("paged_attention",), 0, 10) == 5
    assert trace.busy_ns(HAND, 8, 32) == 9
    # device time inside the decode-step spans: [0,20) holds 15 ns,
    # [25,45) holds 10 ns
    steps = [e for e in HAND["host"] if e[0] == "engine.decode_step"]
    assert trace.device_ns_within(HAND, steps) == 25
    assert trace.top_ops(HAND, 0, 45, k=1) == [["fusion.1", 10e-9]]


def test_load_reads_the_benchmark_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here (CPU: host planes only) loads into the compact
    form with the benchmark's spans in order and no device plane."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("engine.decode_step"):
                f(jnp.ones(4)).block_until_ready()
            with jax.profiler.TraceAnnotation("scheduler"):
                with jax.profiler.TraceAnnotation("prm.score"):
                    pass
    finally:
        jax.profiler.stop_trace()
    compact = trace.load(str(tmp_path))
    names = [e[0] for e in compact["host"]]
    assert names == ["engine.decode_step", "scheduler", "prm.score"] * 2
    assert compact["devices"] == [] and not trace.has_device_ops(compact)
    lo, hi = trace.window(compact)
    assert hi > lo
    assert trace.busy_ns(compact, lo, hi) == 0.0
