"""The reduction from a trace to the per-layer numbers, on a hand-made
trace in the compact form ``trace.load`` produces, with answers worked by
hand; device operations' scope paths, from a hand-made profiler file."""
import sys
from pathlib import Path

import pytest

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


def _xspace(pb2):
    """One chip's ops line, three operations under the scopes of a step
    program (one scope path by reference), and the host's two
    ``engine.decode_step`` spans; times in ns."""
    space = pb2.XSpace()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = "jit(step)/while/body/mlp/dot_general"
    metas = {10: ("fusion.1", "jit(step)/while/body/attn_decode/dot"),
             11: ("fusion.2", None), 12: ("copy.3", "jit(step)/kv_write")}
    for i, (name, path) in metas.items():
        dev.event_metadata[i].id = i
        dev.event_metadata[i].name = name
        if path:
            dev.event_metadata[i].stats.add(metadata_id=1, str_value=path)
    dev.event_metadata[11].stats.add(metadata_id=1, ref_value=2)
    line = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    for meta, start, length in ((10, 0, 10), (11, 5, 10), (12, 30, 10),
                                (10, 42, 3)):
        line.events.add(metadata_id=meta, offset_ps=start * 1000,
                        duration_ps=length * 1000)
    host = space.planes.add(id=2, name="/host:CPU")
    host.event_metadata[1].id = 1
    host.event_metadata[1].name = "engine.decode_step"
    hl = host.lines.add(id=1, name="python3", timestamp_ns=1000)
    for start in (0, 25):
        hl.events.add(metadata_id=1, offset_ps=start * 1000,
                      duration_ps=20_000)
    return space


def test_device_scopes_from_the_ops_metadata(tmp_path):
    """Each device operation's scope path, by value or by reference, rides
    beside ``devices`` (which stays as it was); device time by named
    scope is the union of its operations' intervals."""
    pb2 = trace._xplane_pb2()
    if pb2 is None:
        pytest.skip("no xplane_pb2 in this installation")
    out = tmp_path / "plugins" / "profile" / "1"
    out.mkdir(parents=True)
    (out / "t.xplane.pb").write_bytes(_xspace(pb2).SerializeToString())
    compact = trace.load(str(tmp_path))
    assert compact["devices"] == [[("fusion.1", 1000, 10),
                                   ("fusion.2", 1005, 10),
                                   ("copy.3", 1030, 10),
                                   ("fusion.1", 1042, 3)]]
    attn = {"tf_op": "jit(step)/while/body/attn_decode/dot"}
    assert compact["device_scopes"] == [[
        ("fusion.1", 1000, 10, attn),
        ("fusion.2", 1005, 10,
         {"tf_op": "jit(step)/while/body/mlp/dot_general"}),
        ("copy.3", 1030, 10, {"tf_op": "jit(step)/kv_write"}),
        ("fusion.1", 1042, 3, attn)]]
    lo, hi = trace.window(compact)
    assert (lo, hi) == (1000, 1045)
    assert trace.scope_ns(compact, "attn_decode", lo, hi) == 13
    # the union of [0, 15) and [42, 45), not 10 + 10 + 3
    assert trace.scope_ns(compact, "while", lo, hi) == 18
    assert trace.scope_ns(compact, "attn", lo, hi) == 0     # parts only
    assert trace.scope_ns(compact, "mlp", 1008, hi) == 7
    assert trace.scope_ns(HAND, "mlp", 0, 45) is None
