"""Work counts against numbers worked by hand, for both configurations;
shared prompt pages count once. Counts by kind of layer: a file without
``work`` counts as the same file with it written out, and as the parent
did; a hybrid of Mamba-2, MoE and attention layers worked by hand."""
import re
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from sartbench import roofline, trace  # noqa: E402
from sartbench.spec import (SpecError, describe, load_config,  # noqa: E402
                            load_reader)

QWEN = load_config("qwen2-0.5b")["model"]
QWEN_WORK = describe({"model": QWEN})


def r1_model():
    return load_config("r1-distill-14b-l8")["model"]


def test_layer_flops_by_hand():
    # qwen2-0.5b: q 896x896, k and v 896x128 each, o 896x896, MLP 3 x
    # 896x4864 -> 14,909,440 weights, two FLOPs each
    assert roofline.layer_matmul_flops(QWEN) == 29_818_880
    # r1-distill-14b: q and o 5120x5120, k and v 5120x1024, MLP 3 x
    # 5120x13824 -> 275,251,200 weights
    assert roofline.layer_matmul_flops(r1_model()) == 550_502_400


def test_kv_bytes_per_token_by_hand():
    # K and V, kv heads x head_dim, bf16, per layer
    assert roofline.kv_token_bytes(QWEN) * 24 == 12_288
    assert roofline.kv_token_bytes(r1_model()) * 8 == 32_768


def test_shared_prompt_pages_count_once():
    # rows A and B share prompt pages 5 and 6, then A writes page 9 (8
    # tokens) and B page 10 (4 tokens): 16 + 16 + 8 + 4
    tables = np.array([[5, 6, 9, 99], [5, 6, 10, 99]])
    assert roofline.distinct_tokens(tables, np.array([40, 36]), 16) == 44
    # a shared partial page counts the most tokens any row attends
    tables = np.array([[5, 6, 7], [5, 6, 7]])
    assert roofline.distinct_tokens(tables, np.array([35, 38]), 16) == 38
    # rows of no length read nothing
    assert roofline.distinct_tokens(tables, np.array([0, 0]), 16) == 0


def test_decode_attention_work_by_hand():
    tables = np.array([[5, 6, 9], [5, 6, 10]])
    flops, nbytes = roofline.decode_attention_work(
        QWEN_WORK, tables, np.array([40, 36]), 16)
    # 4 x 14 heads x 64 x (40 + 36) attended keys, 24 layers
    assert flops == 24 * 4 * 14 * 64 * 76 == 6_537_216
    # 44 distinct tokens x 512 B of K+V, plus q in and out (2 rows x 14 x
    # 64 x 2 B, twice), 24 layers
    assert nbytes == 24 * (44 * 512 + 7_168) == 712_704


def test_prefill_attention_work_by_hand():
    # a 32-row lane at position 64 attends 64 + 1 ... 64 + 32 keys
    flops, nbytes = roofline.prefill_attention_work(QWEN_WORK, 64, 32)
    assert flops == 24 * 4 * 14 * 64 * 2576
    assert nbytes == 24 * (96 * 512 + 2 * 32 * 14 * 64 * 2)


def test_step_model_flops_by_hand():
    got = roofline.step_model_flops(QWEN_WORK, 2, 76, [(64, 32)])
    decode_rows = 2 * (24 * 29_818_880 + 2 * 896 * 151_936)
    decode_attn = 24 * 4 * 14 * 64 * 76
    lane = 32 * 24 * 29_818_880 + 2 * 896 * 151_936
    lane_attn = 24 * 4 * 14 * 64 * 2576
    assert got == decode_rows + decode_attn + lane + lane_attn \
        == 25_377_128_448


def test_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_least_time_takes_the_larger_bound():
    kind = "TPU v5 lite"
    assert roofline.least_seconds(197e12, 1.0, kind) == pytest.approx(1.0)
    assert roofline.least_seconds(1.0, 819e9, kind) == pytest.approx(1.0)


# --- counts by kind --------------------------------------------------------

CONFIGS = ("qwen2-0.5b", "r1-distill-14b-l8")
FIXTURE = Path(__file__).resolve().parent / "data" / \
    "qwen2-0.5b.reason-poisson.3steps.json"
KIND = "TPU v5 lite"


def explicit(conf):
    """The configuration with the ``work`` a file without one is read as,
    written out."""
    m = conf["model"]
    L = m["num_layers"]
    return {**conf, "work": [
        {"kind": "attention", "layers": L, "heads": m["num_heads"],
         "kv_heads": m["num_kv_heads"], "head_dim": m["head_dim"]},
        {"kind": "mlp", "layers": L, "width": m["d_ff"], "gated": True}]}


def recorded_run(conf):
    """A run over the recorded three-step trace of the qwen2 cell, with
    steps at its shape: 48 decode rows (six requests of eight branches
    sharing their prompt pages, in 320-page tables) and one 64-row chunk
    lane at 128, drawn from a fixed seed."""
    import json
    from sartbench.cell import Run
    from sartbench.loadgen import Records, Step
    from sartbench.spec import Cell
    rng = np.random.default_rng(20261018)
    steps = []
    for k in range(3):
        tables = np.zeros((48, 320), np.int32)
        lengths = np.zeros(48, np.int32)
        page = 1
        for r0 in range(0, 48, 8):
            prompt = int(rng.integers(64, 1024))
            shared = np.arange(page, page + prompt // 16)
            page += len(shared)
            for r in range(r0, r0 + 8):
                n = prompt + int(rng.integers(1, 3000))
                own = -(-n // 16) - len(shared)
                tables[r, :len(shared)] = shared
                tables[r, len(shared):len(shared) + own] = \
                    np.arange(page, page + own)
                page += own
                lengths[r] = n
        t0 = 1.0 + 0.2 * k
        steps.append(Step(t0, t0 + 0.19, 48, int(lengths.sum()) + 48,
                          [(128, 64)], 48, page, (tables, lengths)))
    rec = Records(t_origin=0.0, t_open=0.5, t_close=30.5, steps=steps,
                  itl=[], tick_time={}, due={}, late=[],
                  trace_span=(0.9, 1.7))
    compact = json.loads(FIXTURE.read_text())
    return Run(Cell("recorded", 1, conf, {}, []), KIND, rec, [], 0.0,
               trace=compact, trace_ns=trace.window(compact))


# the parent's readers (counts of attention plus a gated MLP in every
# layer) on recorded_run: pinned bit for bit
PINNED = {
    "qwen2-0.5b": {"decode_attn_roofline": 1.0349150858765825,
                   "prefill_attn_roofline": 0.45061168846984706,
                   "step_mfu": 0.005222140517360406},
    "r1-distill-14b-l8": {"decode_attn_roofline": 2.7564622203859757,
                          "prefill_attn_roofline": 0.9613049354023403,
                          "step_mfu": 0.029818270104906938},
}


@pytest.mark.parametrize("name", CONFIGS)
def test_explicit_work_counts_as_the_file_without_it(name):
    conf = load_config(name)
    assert "work" not in conf
    bare, full = describe(conf), describe(explicit(conf))
    assert bare == full
    steps = recorded_run(conf).rec.steps
    for s in steps:
        tables, lengths = s.tables
        for fn, args in ((roofline.token_flops, ()),
                         (roofline.step_model_flops, (s.rows, s.ctx,
                                                      s.lanes)),
                         (roofline.decode_attention_work,
                          (tables, lengths, 16)),
                         (roofline.prefill_attention_work, s.lanes[0]),
                         (roofline.step_bytes,
                          (tables, lengths, s.lanes, 16))):
            assert fn(bare, *args) == fn(full, *args)


@pytest.mark.parametrize("name", CONFIGS)
def test_readers_on_recorded_steps_read_as_before(name):
    """With and without an explicit ``work``, the readers read the
    parent's values bit for bit, and ``step_roofline`` reads alike."""
    conf = load_config(name)
    readings = []
    for c in (conf, explicit(conf)):
        run = recorded_run(c)
        got = {m: load_reader(m).read(run) for m in PINNED[name]}
        assert got == PINNED[name]
        readings.append(load_reader("step_roofline").read(run))
    assert readings[0] == readings[1]
    assert 0 < readings[0] < 100


# every per-layer reader BENCHMARK.json named before the program's spans
# and counters reached the records, as the parent read it on
# recorded_run: pinned bit for bit (r1's kv_pages_used reads the qwen2
# steps' pages over r1's smaller pool)
PARENT = {
    "qwen2-0.5b": {"sched_host_ms_per_step": 0.2991833333333333,
                   "engine_host_ms_per_step": 6.289052666666667,
                   "step_device_ms": 190.98097666666666,
                   "step_mfu": 0.005222140517360406,
                   "step_roofline": 1.2594171736577071,
                   "decode_attn_roofline": 1.0349150858765825,
                   "prefill_attn_roofline": 0.45061168846984706,
                   "kv_pages_used": 48.857421875,
                   "device_idle_share": 3.3346470895318503},
    "r1-distill-14b-l8": {"sched_host_ms_per_step": 0.2991833333333333,
                          "engine_host_ms_per_step": 6.289052666666667,
                          "step_device_ms": 190.98097666666666,
                          "step_mfu": 0.029818270104906938,
                          "step_roofline": 5.486039211150366,
                          "decode_attn_roofline": 2.7564622203859757,
                          "prefill_attn_roofline": 0.9613049354023403,
                          "kv_pages_used": 162.85807291666666,
                          "device_idle_share": 3.3346470895318503},
}


@pytest.mark.parametrize("name", CONFIGS)
def test_every_reader_reads_the_parents_float(name):
    """On the recorded trace, with its ``program`` spans and without
    them, each reader the parent had reads the parent's float; the two
    readers of the program's spans and counters read theirs or None."""
    run = recorded_run(load_config(name))
    bare = recorded_run(load_config(name))
    bare.trace = {k: v for k, v in bare.trace.items() if k != "program"}
    for r in (run, bare):
        got = {m: load_reader(m).read(r) for m in PARENT[name]}
        assert got == PARENT[name]
        # the recorded steps carry no counters
        assert load_reader("padded_row_share").read(r) is None
    busy = load_reader("engine_host_busy_ms_per_step")
    assert busy.read(bare) is None
    assert 0 < busy.read(run) < PARENT[name]["engine_host_ms_per_step"]


def test_step_roofline_by_hand():
    """The qwen2 run: the mean least time of the three steps over the
    device time inside the three ``engine.decode_step`` spans."""
    run = recorded_run(load_config("qwen2-0.5b"))
    least = [roofline.least_seconds(
        roofline.step_model_flops(run.work, s.rows, s.ctx, s.lanes),
        roofline.step_bytes(run.work, *s.tables, s.lanes, 16), KIND)
        for s in run.rec.steps]
    spans = [e for e in run.trace["host"] if e[0] == "engine.decode_step"]
    busy = trace.device_ns_within(run.trace, spans) / 1e9
    assert len(spans) == 3 and busy > 0.5
    got = load_reader("step_roofline").read(run)
    assert got == pytest.approx(100 * sum(least) / busy, rel=1e-12)
    # qwen2's weights: 24 layers of 14,909,440 and the tied head of
    # 151,936 x 896, bf16, read once, with no embedding rows of their own
    s = run.rec.steps[0]
    kv = (roofline.distinct_tokens(*s.tables, 16) + 128 + 48 + 64) * 12_288
    assert roofline.step_bytes(run.work, *s.tables, s.lanes, 16) == \
        2 * (24 * 14_909_440 + 151_936 * 896) + kv


# NVIDIA-Nemotron-3-Nano-30B-A3B's first 14 blocks, MEMEM*EMEMEM*E, with
# 16 of its 128 experts on this chip: d_model 2,688, untied vocabulary
# 131,072
NEMOTRON_L14 = {
    "model": {"d_model": 2688, "vocab_size": 131072,
              "tie_embeddings": False, "num_layers": 14, "num_heads": 32,
              "num_kv_heads": 2, "head_dim": 128},
    "work": [
        {"kind": "mamba2", "layers": 6, "heads": 64, "head_dim": 64,
         "state": 128, "groups": 8, "conv": 4},
        {"kind": "moe", "layers": 6, "experts": 128, "held": 16,
         "per_token": 6, "width": 1856, "shared_width": 3712,
         "gated": False},
        {"kind": "attention", "layers": 2, "heads": 32, "kv_heads": 2,
         "head_dim": 128}]}


def test_hybrid_attention_counts_its_attention_layers_only():
    w = describe(NEMOTRON_L14)
    tables = np.array([[5, 6, 9], [5, 6, 10]])
    flops, nbytes = roofline.decode_attention_work(
        w, tables, np.array([40, 36]), 16)
    # 2 attention layers, not 14: 4 x 32 heads x 128 x 76 keys; 44
    # distinct tokens x 1,024 B of K+V plus q in and out (2 rows x 32 x
    # 128 x 2 B, twice)
    assert flops == 2 * 4 * 32 * 128 * 76 == 2_490_368
    assert nbytes == 2 * (44 * 1024 + 32_768) == 155_648
    flops, nbytes = roofline.prefill_attention_work(w, 64, 32)
    assert flops == 2 * 4 * 32 * 128 * 2576 == 84_410_368
    assert nbytes == 2 * (96 * 1024 + 2 * 32 * 32 * 128 * 2) == 1_245_184


def test_hybrid_step_flops_sum_by_kind():
    w = describe(NEMOTRON_L14)
    d = 2688
    # attention: q and o 2688x4096, k and v 2688x256
    attn = 2 * (2 * d * 4096 + 2 * d * 256)
    assert attn == 46_792_704
    # Mamba-2 (d_inner 4,096, 8 groups of state 128, 64 heads): in_proj
    # to z, x, B, C and dt (10,304), out_proj, the conv over x, B and C
    # (6,144 channels, width 4), the state's update and read-out
    mamba = 2 * d * 10_304 + 2 * 4096 * d + 2 * 4 * 6144 + 6 * 64 * 64 * 128
    assert mamba == 80_609_280
    # MoE: the router to 128 experts; 6 of 128 routed experts per token,
    # 16 held here, so 0.75 of one relu^2 expert of 1,856; the shared
    # expert of 3,712
    moe = 2 * d * 128 + 0.75 * 2 * 2 * d * 1856 + 2 * 2 * d * 3712
    assert moe == 55_566_336
    assert roofline.token_flops(w) == 2 * attn + 6 * mamba + 6 * moe \
        == 910_639_104
    # one decode row with no context, and the untied head of 131,072
    assert roofline.step_model_flops(w, 1, 0, []) == \
        910_639_104 + 2 * d * 131_072 == 1_615_282_176
    # against 14 layers of attention and a gated MLP of 1,856
    uniform = 14 * (attn + 2 * 3 * d * 1856) + 2 * d * 131_072
    assert uniform == 1_778_810_880


def test_hybrid_state_and_weight_bytes():
    w = describe(NEMOTRON_L14)
    (mamba,), (moe,) = w.of("mamba2"), w.of("moe")
    # 64 x 64 x 128 of SSM state and 3 x 6,144 conv inputs, bf16
    assert roofline.mamba2_state_bytes(mamba) == \
        (64 * 64 * 128 + 3 * 6144) * 2 == 1_085_440
    # without a record the routed experts count zero: the router and the
    # shared expert of each of 6 layers
    assert roofline.weight_bytes(moe, 2688) == \
        6 * (2688 * 128 + 2 * 2688 * 3712) * 2 == 243_597_312
    # each routed expert reached adds its two 2,688 x 1,856 matrices
    assert roofline.weight_bytes(moe, 2688, reached=10) == \
        243_597_312 + 10 * 2 * 2688 * 1856 * 2
    # one decode row of 40 tokens: the head, the row's embedding, the
    # attention weights and 41 tokens' K/V in 2 layers, the Mamba-2
    # weights and the row's state read and written in 6, the MoE above
    got = roofline.step_bytes(w, np.array([[5, 6, 9]]), np.array([40]),
                              [], 16)
    assert got == (131_072 * 2688 * 2 + 2688 * 2
                   + 2 * 23_396_352 * 2 + 2 * 41 * 1024
                   + 6 * (2688 * 10_304 + 4096 * 2688 + 4 * 6144) * 2
                   + 6 * 2 * 1_085_440 + 243_597_312) == 1_519_721_728
    assert roofline.step_bytes(w, np.array([[5, 6, 9]]), np.array([40]),
                               [], 16, reached=[10]) == \
        got + 10 * 2 * 2688 * 1856 * 2


@pytest.mark.parametrize("group, words", [
    ({"kind": "ssm", "layers": 2}, "unknown kind"),
    ({"kind": "mlp", "layers": 2, "width": 64}, "missing ['gated']"),
    ({"kind": "mlp", "layers": 2, "width": 64, "gated": True,
      "heads": 4}, "not of this kind ['heads']"),
    ({"kind": "mlp", "layers": 0, "width": 64, "gated": True}, "layers"),
    ({"kind": "moe", "layers": 2, "experts": 8, "held": 16,
      "per_token": 2, "width": 64, "shared_width": 0, "gated": True},
     "at most experts"),
    # attention widths other than the model's, whose K/V pool is timed
    ({"kind": "attention", "layers": 24, "heads": 14, "kv_heads": 2,
      "head_dim": 128}, "differ from model's (14, 2, 64)"),
    ({"kind": "attention", "layers": 24, "heads": 14, "kv_heads": 14,
      "head_dim": 64}, "(14, 14, 64) differ"),
])
def test_a_bad_work_is_refused(group, words):
    conf = {"model": QWEN, "work": [group]}
    with pytest.raises(SpecError, match=re.escape(words)):
        describe(conf)


# --- routed experts reached, by the program's counter ----------------------


def moe_run(config=NEMOTRON_L14):
    return recorded_run({**config, "engine": {"page_size": 16,
                                              "num_pages": 10240}})


def test_routed_expert_bytes_follow_the_counter():
    """A step whose record holds ``moe.experts_reached`` counts that many
    routed experts' weights; without it, or with other counters alone, the
    step reads as before."""
    reader = load_reader("step_roofline")
    run = moe_run()
    w = run.work
    before = reader.read(run)
    for s in run.rec.steps:
        s.counts = {"rows_computed": 112, "rows_useful": 110}
    assert reader.read(run) == before
    reached = [40, 61, 96]              # 6 MoE layers x 16 held = 96 at most
    for s, n in zip(run.rec.steps, reached):
        s.counts = {roofline.EXPERTS_REACHED: n}
    spans = [e for e in run.trace["host"] if e[0] == "engine.decode_step"]
    busy = trace.device_ns_within(run.trace, spans) / 1e9
    least = [roofline.least_seconds(
        roofline.step_model_flops(w, s.rows, s.ctx, s.lanes),
        roofline.step_bytes(w, *s.tables, s.lanes, 16, reached=[n]), KIND)
        for s, n in zip(run.rec.steps, reached)]
    got = reader.read(run)
    assert got == pytest.approx(100 * sum(least) / busy, rel=1e-12)
    assert got > before
    # each routed expert reached: two 2,688 x 1,856 matrices, bf16
    s = run.rec.steps[0]
    assert roofline.step_bytes(w, *s.tables, s.lanes, 16,
                               roofline.experts_reached(w, s.counts)) == \
        roofline.step_bytes(w, *s.tables, s.lanes, 16) + \
        40 * 2 * 2688 * 1856 * 2


def test_experts_reached_by_configuration():
    counts = {roofline.EXPERTS_REACHED: 12}
    # no MoE group: the counter is not read
    assert roofline.experts_reached(QWEN_WORK, counts) is None
    nemo = describe(NEMOTRON_L14)
    assert roofline.experts_reached(nemo, counts) == [12]
    assert roofline.experts_reached(nemo, {}) is None
    with pytest.raises(ValueError, match="97 in one step"):
        roofline.experts_reached(nemo, {roofline.EXPERTS_REACHED: 97})
    # two MoE groups: the sum cannot be split between them
    moe = next(g for g in NEMOTRON_L14["work"] if g["kind"] == "moe")
    two = describe({**NEMOTRON_L14,
                    "work": NEMOTRON_L14["work"] + [dict(moe, layers=1)]})
    assert roofline.experts_reached(two, {}) is None
    with pytest.raises(SpecError, match="2 MoE groups"):
        roofline.experts_reached(two, counts)
