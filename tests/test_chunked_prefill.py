"""Chunked-bucketed prefill: equivalence with the exact-length path (all
model families — attention pad rows drop their page writes, ssm/hybrid pad
rows are masked-dt identity transitions), O(1) compile count in
prompt-length diversity, and decode-step piggybacking that never perturbs
running branches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kv import OutOfPagesError
from repro.models import Model
from repro.serving import Engine, EngineConfig, SamplingParams

from conftest import FAMILY_CONFIGS, tiny_config

FAMILIES = {k: FAMILY_CONFIGS[k] for k in ("dense", "ssm", "hybrid")}


def _engine(cfg, temperature=0.0, slots=4, seed=0, **eng_kw):
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    base = dict(page_size=4, num_pages=128, max_slots=slots,
                max_pages_per_branch=24, eos_id=1,
                sampling=SamplingParams(temperature=temperature), seed=seed,
                prefill_chunk=8)
    base.update(eng_kw)
    return model, params, Engine(model, params, EngineConfig(**base))


def _assert_ssm_close(ssm_a, ssm_b, atol=1e-5):
    assert (ssm_a is None) == (ssm_b is None)
    if ssm_a is not None:
        for got, want in zip(ssm_a, ssm_b):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=atol)


def _gather_prefix(eng, blocks, s):
    """Dense [L, s, kv, hd] view of the first s tokens of a branch's pages."""
    ps = eng.cfg.page_size
    k = np.asarray(eng.state["k_pages"])[:, :, blocks.pages]  # [L,kv,n,ps,hd]
    v = np.asarray(eng.state["v_pages"])[:, :, blocks.pages]
    k = np.moveaxis(k, 1, 3).reshape(k.shape[0], -1, k.shape[1], k.shape[-1])
    v = np.moveaxis(v, 1, 3).reshape(v.shape[0], -1, v.shape[1], v.shape[-1])
    return k[:, :s], v[:, :s]


# ragged lengths crossing page (ps=4), chunk (8) and bucket (4/8) boundaries
RAGGED = [1, 3, 4, 5, 7, 8, 9, 12, 13, 17, 23]


@pytest.mark.parametrize("s", RAGGED)
def test_chunked_matches_exact_prefill(s):
    """Same params, same prompt: the chunked-bucketed path must reproduce
    the exact-length program's last logits AND the K/V page contents."""
    cfg = tiny_config()
    rng = np.random.default_rng(s)
    prompt = [int(t) for t in rng.integers(2, cfg.vocab_size, size=s)]

    _, _, e_exact = _engine(cfg)
    _, _, e_chunk = _engine(cfg)
    b_e, lg_e, _ = e_exact.prefill(prompt, exact=True)
    b_c, lg_c, _ = e_chunk.prefill(prompt)          # chunked by default

    np.testing.assert_allclose(np.asarray(lg_e), np.asarray(lg_c),
                               rtol=1e-4, atol=1e-4)
    ke, ve = _gather_prefix(e_exact, b_e, s)
    kc, vc = _gather_prefix(e_chunk, b_c, s)
    np.testing.assert_allclose(ke, kc, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ve, vc, rtol=1e-4, atol=1e-5)

    e_exact.release_prefix(b_e)
    e_chunk.release_prefix(b_c)
    assert e_chunk.allocator.used_pages == 0


@pytest.mark.parametrize("family", ["ssm", "hybrid"])
@pytest.mark.parametrize("s", [1, 4, 5, 8, 13, 17])
def test_chunked_matches_exact_prefill_ssm(family, s):
    """ssm/hybrid prompts through the masked-dt chunk lane must reproduce
    the exact-length program's last logits AND final per-layer (conv, ssd)
    state across ragged lengths spanning chunk/bucket/page boundaries."""
    cfg = tiny_config(**FAMILIES[family])
    rng = np.random.default_rng(s)
    prompt = [int(t) for t in rng.integers(2, cfg.vocab_size, size=s)]

    _, _, e_exact = _engine(cfg)
    _, _, e_chunk = _engine(cfg)
    b_e, lg_e, ssm_e = e_exact.prefill(prompt, exact=True)
    b_c, lg_c, ssm_c = e_chunk.prefill(prompt)      # chunked by default

    np.testing.assert_allclose(np.asarray(lg_e), np.asarray(lg_c),
                               rtol=1e-4, atol=1e-4)
    _assert_ssm_close(ssm_e, ssm_c)
    if cfg.uses_attention:
        ke, ve = _gather_prefix(e_exact, b_e, s)
        kc, vc = _gather_prefix(e_chunk, b_c, s)
        np.testing.assert_allclose(ke, kc, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(ve, vc, rtol=1e-4, atol=1e-5)
    assert len(e_chunk._prefill_cache) == 0         # exact path never used

    e_exact.release_prefix(b_e)
    e_chunk.release_prefix(b_c)
    assert e_chunk.allocator.used_pages == 0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_chunked_then_decode_matches_exact_then_decode(family):
    """Greedy generation after a chunked prefill equals generation after an
    exact prefill — the pages and SSM state it left behind are a faithful
    cache."""
    cfg = tiny_config(**FAMILIES[family])
    prompt = [2, 5, 9, 13, 7, 3, 11, 4, 8, 6, 10]   # 11 tokens: 2 chunks

    def gen(exact):
        _, _, eng = _engine(cfg, temperature=0.0)
        blocks, lg, ssm = eng.prefill(prompt, exact=exact)
        h = eng.spawn_branch(0, blocks, lg, ssm, len(prompt))
        for _ in range(8):
            eng.decode_step()
        toks = list(h.tokens)
        eng.free_branch(h)
        eng.release_prefix(blocks)
        assert eng.allocator.used_pages == 0
        return toks

    assert gen(exact=True) == gen(exact=False)


@pytest.mark.parametrize("family,n_lengths", [
    ("dense", 16), ("ssm", 8), ("hybrid", 8)])
def test_compile_count_is_o_num_buckets(family, n_lengths):
    """Acceptance: prompts of distinct ragged lengths trace at most 4
    prefill/mixed-step shapes (the seed's exact path traced one per
    length) — for every model family, ssm/hybrid included."""
    cfg = tiny_config(**FAMILIES[family])
    _, _, eng = _engine(cfg, slots=2, num_pages=256,
                        max_pages_per_branch=32)
    lengths = list(range(3, 3 + n_lengths))         # distinct lengths
    rng = np.random.default_rng(0)
    for s in lengths:
        prompt = [int(t) for t in rng.integers(2, cfg.vocab_size, size=s)]
        blocks, lg, ssm = eng.prefill(prompt)
        eng.release_prefix(blocks)
    assert eng.prefill_compile_count <= 4
    assert len(eng._prefill_cache) == 0             # exact path never used
    assert eng.allocator.used_pages == 0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_piggybacked_prefill_leaves_decode_untouched(family):
    """A prompt admitted mid-generation rides the decode step as extra rows;
    the running branch's greedy continuation must be bit-identical to a run
    with no concurrent prefill, and the admitted prompt must produce the
    same logits as a standalone prefill. For ssm/hybrid this additionally
    pins that the chunk lane's carried state never bleeds into the per-slot
    (conv, ssd) rows of live branches."""
    cfg = tiny_config(**FAMILIES[family])
    prompt_a = [2, 5, 9, 13, 7]
    prompt_b = [3, 8, 11, 6, 12, 4, 10, 9, 2, 7, 5, 13, 3]   # 13 tokens

    def run(piggyback):
        _, _, eng = _engine(cfg, temperature=0.0)
        blocks, lg, ssm = eng.prefill(prompt_a)
        h = eng.spawn_branch(0, blocks, lg, ssm, len(prompt_a))
        for _ in range(3):
            eng.decode_step()
        st = eng.begin_prefill(prompt_b) if piggyback else None
        for _ in range(6):                          # covers the 2 chunks
            eng.decode_step()
        lg_b = None
        if piggyback:
            assert st.done
            b_b, lg_b, _ = eng.finish_prefill(st)
            eng.release_prefix(b_b)
        toks = list(h.tokens)
        eng.free_branch(h)
        eng.release_prefix(blocks)
        assert eng.allocator.used_pages == 0
        return toks, lg_b

    toks_plain, _ = run(piggyback=False)
    toks_mixed, lg_b = run(piggyback=True)
    assert toks_plain == toks_mixed

    _, _, ref = _engine(cfg)
    b_ref, lg_ref, _ = ref.prefill(prompt_b)
    np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg_b),
                               rtol=1e-4, atol=1e-4)
    ref.release_prefix(b_ref)


@pytest.mark.parametrize("kernel", ["fused", "decode"])
@pytest.mark.parametrize("family", ["dense", "hybrid"])
@pytest.mark.parametrize("s", [8, 9, 12, 16, 7])
def test_chunk_visible_context_pinned_at_page_edges(family, kernel, s):
    """Regression pinning the exact visible-context length of mixed-step
    chunk rows at chunk/page boundaries (page_size=4, prefill_chunk=8, so
    chunk boundaries land on and straddle page edges).

    Chunk rows attend pages the step itself just wrote, so an off-by-one in
    the causal horizon at a page edge would read one future (unwritten)
    slot. Before the final chunk, every not-yet-written slot of the
    request's pre-allocated pages is poisoned with huge values: the final
    chunk must overwrite exactly its own positions and mask everything
    past each row's own position, leaving the last logits equal to the
    exact-length prefill's."""
    cfg = tiny_config(**FAMILIES[family])
    rng = np.random.default_rng(s)
    prompt = [int(t) for t in rng.integers(2, cfg.vocab_size, size=s)]

    _, _, e_exact = _engine(cfg)
    b_e, lg_e, _ = e_exact.prefill(prompt, exact=True)

    _, _, eng = _engine(cfg, mixed_step_kernel=kernel)
    st = eng.begin_prefill(prompt)
    while st.remaining > eng.cfg.prefill_chunk:
        eng.decode_step()
    ps = eng.cfg.page_size
    pages = np.asarray(st.blocks.pages, np.int64)
    poison = np.zeros(eng.state["k_pages"].shape[2:4], bool)  # [P, ps]
    for pos in range(st.next_pos, len(pages) * ps):
        poison[pages[pos // ps], pos % ps] = True
    pz = jnp.asarray(poison)[None, None, :, :, None]
    for key in ("k_pages", "v_pages"):
        eng.state[key] = jnp.where(pz, 1e4, eng.state[key])
    while not st.done:
        eng.decode_step()
    b_c, lg_c, _ = eng.finish_prefill(st)

    np.testing.assert_allclose(np.asarray(lg_e), np.asarray(lg_c),
                               rtol=1e-4, atol=1e-4)
    e_exact.release_prefix(b_e)
    eng.release_prefix(b_c)


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_fused_mixed_step_matches_decode_path(family):
    """Equivalence of the two mixed_step_kernel paths: same seed, same
    workload (a branch decoding greedily while a second prompt piggybacks)
    must produce bit-identical branch tokens, fp32-close harvested logits,
    and fp32-close K/V pages for the admitted prompt."""
    cfg = tiny_config(**FAMILIES[family])
    prompt_a = [2, 5, 9, 13, 7]
    prompt_b = [3, 8, 11, 6, 12, 4, 10, 9, 2, 7, 5, 13, 3]   # 13 tokens

    def run(kernel):
        _, _, eng = _engine(cfg, temperature=0.0, mixed_step_kernel=kernel)
        blocks, lg, ssm = eng.prefill(prompt_a)
        h = eng.spawn_branch(0, blocks, lg, ssm, len(prompt_a))
        for _ in range(3):
            eng.decode_step()
        st = eng.begin_prefill(prompt_b)
        while not st.done:
            eng.decode_step()
        b_b, lg_b, _ = eng.finish_prefill(st)
        kb, vb = _gather_prefix(eng, b_b, len(prompt_b))
        toks = list(h.tokens)
        eng.free_branch(h)
        eng.release_prefix(blocks)
        eng.release_prefix(b_b)
        return toks, np.asarray(lg_b), kb, vb

    toks_f, lg_f, k_f, v_f = run("fused")
    toks_d, lg_d, k_d, v_d = run("decode")
    assert toks_f == toks_d
    np.testing.assert_allclose(lg_f, lg_d, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(k_f, k_d, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(v_f, v_d, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_multi_lane_matches_single_lane(family):
    """Token-budget lane packing (step_token_budget for 2 concurrent chunk
    lanes) must reproduce the single-lane path's harvested logits, final
    SSM state AND K/V page contents for every admitted prompt — across
    ragged lengths and all model families."""
    cfg = tiny_config(**FAMILIES[family])
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(2, cfg.vocab_size, size=s)]
               for s in (13, 9, 17)]

    _, _, single = _engine(cfg)                      # legacy FIFO lane
    want = [single.prefill(p) for p in prompts]

    _, _, multi = _engine(cfg, step_token_budget=16)  # 2 lanes x bucket 8
    assert multi.admission_capacity == 2
    sts = [multi.begin_prefill(p) for p in prompts]
    single_steps = sum(-(-len(p) // 8) for p in prompts)
    steps = 0
    while any(not st.done for st in sts):
        multi.decode_step()
        steps += 1
    assert steps < single_steps, "packing never carried 2 lanes"
    for st, p, (b_w, lg_w, ssm_w) in zip(sts, prompts, want):
        b_m, lg_m, ssm_m = multi.finish_prefill(st)
        np.testing.assert_allclose(np.asarray(lg_w), np.asarray(lg_m),
                                   rtol=1e-4, atol=1e-4)
        _assert_ssm_close(ssm_w, ssm_m)
        if cfg.uses_attention:
            kw, vw = _gather_prefix(single, b_w, len(p))
            km, vm = _gather_prefix(multi, b_m, len(p))
            np.testing.assert_allclose(kw, km, rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(vw, vm, rtol=1e-4, atol=1e-5)
        multi.release_prefix(b_m)
    assert multi.allocator.used_pages == 0


def test_lane_budget_one_chunk_is_bit_exact_with_fifo_engine():
    """Acceptance: step_token_budget sized for exactly one chunk keeps the
    live engine bit-exact with the legacy FIFO lane — same branch tokens,
    same harvested logits, same rng stream."""
    cfg = tiny_config()
    prompt_a = [2, 5, 9, 13, 7]
    prompt_b = [3, 8, 11, 6, 12, 4, 10, 9, 2, 7, 5, 13, 3]

    def run(budget):
        _, _, eng = _engine(cfg, temperature=0.0, step_token_budget=budget)
        assert eng.admission_capacity == 1
        blocks, lg, ssm = eng.prefill(prompt_a)
        h = eng.spawn_branch(0, blocks, lg, ssm, len(prompt_a))
        for _ in range(3):
            eng.decode_step()
        st = eng.begin_prefill(prompt_b)
        while not st.done:
            eng.decode_step()
        _, lg_b, _ = eng.finish_prefill(st)
        return list(h.tokens), np.asarray(lg_b)

    toks_fifo, lg_fifo = run(0)
    toks_one, lg_one = run(8)            # budget == one bucket-8 chunk
    assert toks_fifo == toks_one
    np.testing.assert_array_equal(lg_fifo, lg_one)


def test_lane_budget_below_bucket_rejected():
    cfg = tiny_config()
    with pytest.raises(ValueError, match="cannot carry even one full"):
        _engine(cfg, step_token_budget=4)            # max bucket is 8
    with pytest.raises(ValueError, match="must include 1"):
        _engine(cfg, step_token_budget=16, chunk_lane_configs=(2,))
    # configs the packer can never fill would make admission_capacity
    # over-reserve prompts' pages — rejected at construction
    with pytest.raises(ValueError, match="exceed the"):
        _engine(cfg, chunk_lane_configs=(1, 4))      # budget 0: FIFO only
    with pytest.raises(ValueError, match="exceed the"):
        _engine(cfg, step_token_budget=16, chunk_lane_configs=(1, 8))
    # a budget without chunked admission is contradictory: sync prefill
    # has no lanes, and capacity > 1 would drain the scheduler's arrival
    # queue in one tick
    with pytest.raises(ValueError, match="requires chunked_prefill"):
        _engine(cfg, chunked_prefill=False, step_token_budget=16)


# ------------------------------------------------------ radix prefix cache


def _drain(eng, prompt):
    """Admit + drain one prompt; returns (state, steps_taken)."""
    st = eng.begin_prefill(prompt)
    steps = 0
    while not st.done:
        eng.decode_step()
        steps += 1
    eng.finish_prefill(st)
    return st, steps


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_prefix_cache_bit_exact_on_vs_off(family):
    """Acceptance: cache-on must reproduce cache-off bit-exactly — same
    greedy branch tokens, same harvested logits, same final K/V page
    contents and SSM state — while serving the shared header from cached
    pages (fewer chunk steps, hit_tokens > 0)."""
    cfg = tiny_config(**FAMILIES[family])
    rng = np.random.default_rng(3)
    header = [int(t) for t in rng.integers(2, cfg.vocab_size, size=10)]
    prompts = [header + [3, 7, 2, 9], header + [5, 2, 8, 4, 6]]

    def run(cache):
        _, _, eng = _engine(cfg, temperature=0.0, prefix_cache=cache)
        outs, steps = [], []
        for p in prompts:
            st, n = _drain(eng, p)
            steps.append(n)
            kv = (_gather_prefix(eng, st.blocks, len(p))
                  if cfg.uses_attention else None)
            outs.append((np.asarray(st.last_logits), st.ssm_state, kv))
        return eng, outs, steps

    eng_off, outs_off, steps_off = run(False)
    eng_on, outs_on, steps_on = run(True)
    assert sum(steps_on) < sum(steps_off), "warm admission saved no steps"
    assert eng_on.prefix_cache.stats()["hit_tokens"] > 0
    for (lg_a, ssm_a, kv_a), (lg_b, ssm_b, kv_b) in zip(outs_off, outs_on):
        np.testing.assert_array_equal(lg_a, lg_b)
        assert (ssm_a is None) == (ssm_b is None)
        if ssm_a is not None:
            for got, want in zip(ssm_a, ssm_b):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
        if kv_a is not None:
            np.testing.assert_array_equal(kv_a[0], kv_b[0])
            np.testing.assert_array_equal(kv_a[1], kv_b[1])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_prefix_cache_greedy_decode_matches(family):
    """Branches spawned off a warm-hit prefix decode the same greedy
    tokens as off a cold prefill (the cached pages and seeded SSM state
    are a faithful KV substrate, not just matching logits)."""
    cfg = tiny_config(**FAMILIES[family])
    header = [2, 5, 9, 13, 7, 3, 11, 4]         # 2 pages (page_size 4)
    prompt = header + [8, 6, 10]

    def gen(cache, warm):
        _, _, eng = _engine(cfg, temperature=0.0, prefix_cache=cache)
        if warm:                                 # populate + idle the cache
            st, _ = _drain(eng, header + [12, 2])
            eng.release_prefix(st.blocks)
            assert eng.prefix_cache.evictable > 0
        st, _ = _drain(eng, prompt)
        assert (st.cached_tokens > 0) == warm
        h = eng.spawn_branch(0, st.blocks, st.last_logits, st.ssm_state,
                             len(prompt))
        for _ in range(8):
            eng.decode_step()
        toks = list(h.tokens)
        eng.free_branch(h)
        eng.release_prefix(st.blocks)
        assert eng.allocator.used_pages == 0
        eng.allocator.check_invariants()
        return toks

    assert gen(cache=False, warm=False) == gen(cache=True, warm=True)


def test_prefix_cache_resurrects_idle_pages_without_rewrite():
    """decref-to-LRU at engine level: releasing every reference parks the
    prompt's full pages on the cache LRU (used_pages drains to 0), and
    re-admitting the same prompt resurrects them — identical logits with
    only the capped tail recomputed and zero K/V rewrites for the rest."""
    cfg = tiny_config()
    _, _, eng = _engine(cfg, prefix_cache=True)
    prompt = [2, 5, 9, 13, 7, 3, 11, 4, 8, 6, 10, 12, 3, 7]   # 14 tokens
    st1, _ = _drain(eng, prompt)
    lg1 = np.asarray(st1.last_logits)
    eng.release_prefix(st1.blocks)
    assert eng.allocator.used_pages == 0
    assert eng.prefix_cache.evictable == 3      # 3 full pages parked
    st2, steps2 = _drain(eng, prompt)
    # capped reuse: (14-1)//4 = 3 pages = 12 tokens; 2-token tail recomputed
    assert st2.cached_tokens == 12 and steps2 == 1
    assert eng.prefix_cache.stats()["resurrections"] == 3
    np.testing.assert_array_equal(lg1, np.asarray(st2.last_logits))
    eng.release_prefix(st2.blocks)
    eng.allocator.check_invariants()


def test_prefix_cache_evicts_under_page_pressure_only():
    """A full pool reclaims idle cached pages instead of raising; pages
    still referenced by live branches are never victims; truly exhausted
    pools still raise OutOfPagesError with nothing allocated."""
    cfg = tiny_config()
    _, _, eng = _engine(cfg, num_pages=8, prefix_cache=True)
    st1, _ = _drain(eng, [2, 5, 9, 13, 7, 3, 11, 4])   # 2 pages, both full
    eng.release_prefix(st1.blocks)                      # -> LRU
    assert eng.prefix_cache.evictable == 2
    # 7 pages of new prompt force evictions of the idle pages
    st2, _ = _drain(eng, [6] * 26)
    assert eng.prefix_cache.stats()["evictions"] >= 1
    eng.allocator.check_invariants()
    # live pages are not reclaimable: an oversized prompt still fails fast
    with pytest.raises(OutOfPagesError):
        eng.begin_prefill([7] * 32)
    assert not eng.has_pending_prefill
    eng.allocator.check_invariants()
    eng.release_prefix(st2.blocks)
    assert eng.allocator.used_pages == 0


def test_prefix_cache_ssm_reuse_gated_on_boundary_state():
    """ssm/hybrid reuse is truncated to the deepest page boundary with a
    stored (conv, ssd) snapshot — page boundaries between chunk
    boundaries have attention K/V but no seedable recurrence state."""
    cfg = tiny_config(**FAMILIES["hybrid"])
    _, _, eng = _engine(cfg, prefix_cache=True)   # chunk 8, page 4
    prompt = [2, 5, 9, 13, 7, 3, 11, 4, 8, 6, 10, 12, 3, 7]   # 14 tokens
    st1, _ = _drain(eng, prompt)
    eng.release_prefix(st1.blocks)
    st2, _ = _drain(eng, prompt)
    # dense would reuse 12 tokens (3 pages); the hybrid resumes at the
    # page-aligned chunk boundary 8 where a snapshot exists
    assert st2.cached_tokens == 8
    np.testing.assert_array_equal(np.asarray(st1.last_logits),
                                  np.asarray(st2.last_logits))
    eng.release_prefix(st2.blocks)
    eng.allocator.check_invariants()


def test_prefix_cache_single_page_dispatch_per_mixed_step():
    """Acceptance pin: chunk K/V writes AND the step's CoW page copies
    execute inside the one jit'd step program — after every decode_step
    (any lane count, CoWs pending or not) the engine's page arrays are
    exactly the objects that single dispatch returned; no host-side copy
    ever touches them."""
    cfg = tiny_config()
    _, _, eng = _engine(cfg, temperature=0.0, step_token_budget=16)
    blocks, lg, ssm = eng.prefill([2, 5, 9])    # 3 tokens: partial page
    h1 = eng.spawn_branch(0, blocks, lg, ssm, 3)
    h2 = eng.spawn_branch(0, blocks, lg, ssm, 3)   # shared partial -> CoW
    sts = [eng.begin_prefill([3 + i] * 13) for i in range(2)]

    captured = []
    orig = eng._step_jit

    def spy(*args, **kw):
        out = orig(*args, **kw)
        captured.append(out[3])                 # the step's new state
        return out

    eng._step_jit = spy
    saw_multi_lane = False
    while any(not st.done for st in sts):
        before = eng.prefill_chunk_steps
        eng.decode_step()
        saw_multi_lane |= eng.prefill_chunk_steps - before > 1
        assert len(captured) == eng.decode_steps_executed, \
            "a decode step issued more than one device dispatch"
        assert eng.state["k_pages"] is captured[-1]["k_pages"]
        assert eng.state["v_pages"] is captured[-1]["v_pages"]
    assert saw_multi_lane, "no mixed step ever carried 2 lanes"
    for st in sts:
        eng.release_prefix(st.blocks)
    eng.free_branch(h1)
    eng.free_branch(h2)
    eng.release_prefix(blocks)
    assert eng.allocator.used_pages == 0


def test_prefix_cache_oversized_prompt_acquires_nothing():
    """Regression: a prompt exceeding the block-table width must fail
    BEFORE acquiring cached-prefix references — an assert after acquire
    would leak increfed pages no release path ever returns."""
    cfg = tiny_config()
    _, _, eng = _engine(cfg, prefix_cache=True)
    st, _ = _drain(eng, [2] * 12)               # populate the cache
    eng.release_prefix(st.blocks)
    idle = eng.prefix_cache.evictable
    assert idle > 0
    with pytest.raises(AssertionError, match="block-table width"):
        eng.begin_prefill([2] * 200)            # shares the cached prefix
    assert eng.prefix_cache.evictable == idle   # nothing acquired
    assert eng.allocator.used_pages == 0
    eng.allocator.check_invariants()


def test_prefix_cache_requires_chunked_prefill():
    cfg = tiny_config()
    with pytest.raises(ValueError, match="prefix_cache requires"):
        _engine(cfg, chunked_prefill=False, prefix_cache=True)


def test_mixed_step_kernel_validated():
    cfg = tiny_config()
    with pytest.raises(AssertionError):
        _engine(cfg, mixed_step_kernel="nope")


def test_pending_prefills_complete_fifo():
    """Several admitted prompts drain one chunk per step, oldest first."""
    cfg = tiny_config()
    _, _, eng = _engine(cfg, slots=2)
    sts = [eng.begin_prefill([2 + i] * (6 + 3 * i)) for i in range(3)]
    done_order = []
    for _ in range(12):
        eng.decode_step()
        for i, st in enumerate(sts):
            if st.done and i not in done_order:
                done_order.append(i)
    assert done_order == [0, 1, 2]
    for st in sts:
        eng.release_prefix(st.blocks)
    assert eng.allocator.used_pages == 0


def test_abort_prefill_releases_pages():
    cfg = tiny_config()
    _, _, eng = _engine(cfg)
    st = eng.begin_prefill([2, 5, 9, 13, 7, 3, 11, 4, 8])
    eng.decode_step()                               # first chunk in flight
    eng.abort_prefill(st)
    assert eng.allocator.used_pages == 0
    assert not eng.has_pending_prefill


@pytest.mark.parametrize("family", ["ssm", "hybrid"])
def test_ssm_configs_admit_async(family):
    """ssm/hybrid prompts now ride the bucketed chunk lane (masked-dt scan):
    begin_prefill queues instead of stalling, chunks drain one per decode
    step, and the harvested state carries the final SSM state for
    spawn_branch."""
    cfg = tiny_config(**FAMILIES[family])
    _, _, eng = _engine(cfg)
    st = eng.begin_prefill([2, 5, 9, 13, 7, 3, 11, 4, 8])  # 9 tok: 2 chunks
    assert not st.done and eng.has_pending_prefill
    steps = 0
    while not st.done:
        eng.decode_step()
        steps += 1
    assert steps == 2
    blocks, lg, ssm = eng.finish_prefill(st)
    assert ssm is not None and lg is not None
    assert eng.prefill_compile_count <= 2
    h = eng.spawn_branch(0, blocks, lg, ssm, 9)
    for _ in range(3):
        eng.decode_step()
    eng.free_branch(h)
    eng.release_prefix(blocks)
    assert eng.allocator.used_pages == 0


def test_chunked_prefill_disabled_is_synchronous():
    """chunked_prefill=False keeps the seed's synchronous exact-length
    admission for every family."""
    cfg = tiny_config(**FAMILIES["ssm"])
    _, _, eng = _engine(cfg, chunked_prefill=False)
    st = eng.begin_prefill([2, 5, 9, 13, 7])
    assert st.done and st.ssm_state is not None
    assert not eng.has_pending_prefill
    eng.release_prefix(st.blocks)
    assert eng.allocator.used_pages == 0


def test_abort_after_harvest_does_not_double_release():
    """Regression: aborting a state whose pages were already harvested (and
    forked by spawn_branch) must NOT release them again — that would decref
    pages live branches still reference and corrupt the pool."""
    cfg = tiny_config()
    _, _, eng = _engine(cfg)
    st = eng.begin_prefill([2, 5, 9, 13, 7, 3, 11, 4, 8])
    while not st.done:
        eng.decode_step()
    blocks, lg, ssm = eng.finish_prefill(st)
    h = eng.spawn_branch(0, blocks, lg, ssm, 9)
    used = eng.allocator.used_pages
    eng.abort_prefill(st)             # late abort: queue no-op, pages kept
    assert eng.allocator.used_pages == used
    eng.allocator.check_invariants()
    for _ in range(4):
        eng.decode_step()             # branch must still decode fine
    eng.free_branch(h)
    eng.release_prefix(blocks)
    assert eng.allocator.used_pages == 0
    eng.allocator.check_invariants()


def test_abort_prefill_is_idempotent():
    cfg = tiny_config()
    _, _, eng = _engine(cfg)
    st = eng.begin_prefill([2, 5, 9, 13, 7])
    eng.abort_prefill(st)
    eng.abort_prefill(st)             # BranchBlocks already emptied: no-op
    assert eng.allocator.used_pages == 0
    eng.allocator.check_invariants()


def test_bucket_overflow_raises():
    """A chunk longer than the largest bucket must fail loudly — silently
    padding to the top bucket would alias several prompt positions onto one
    step row."""
    cfg = tiny_config()
    _, _, eng = _engine(cfg)                        # buckets (4, 8)
    assert eng._bucket_for(8) == 8                  # boundary: exact fit
    with pytest.raises(ValueError, match="exceeds the largest"):
        eng._bucket_for(9)
    # misconfiguration is rejected at construction, before any admission
    with pytest.raises(ValueError, match="must cover a full"):
        _engine(cfg, prefill_buckets=(2, 4))        # top < prefill_chunk=8


# --------------------------------------- tree decode + generated-prefix cache


def _branch_state(eng, handles):
    """(tokens, page lists, K/V contents, SSM rows) of every live branch."""
    out = []
    for h in handles:
        kv = (_gather_prefix(eng, h.blocks, h.blocks.length)
              if eng.model.cfg.uses_attention else None)
        ssm = None
        if eng.model.cfg.uses_ssm:
            ssm = (np.asarray(eng.state["conv"][:, h.slot]),
                   np.asarray(eng.state["ssd"][:, h.slot]))
        out.append((list(h.tokens), list(h.blocks.pages), kv, ssm))
    return out


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_tree_decode_kernel_bit_exact_on_vs_off(family):
    """decode_kernel="tree" must be bit-exact with the per-branch paged
    path under a forking workload — same sampled tokens, same page lists,
    same K/V page contents, same SSM rows. (For the pure-SSM family the
    tree map is empty and the config must degrade to a no-op.)"""
    cfg = tiny_config(**FAMILIES[family])
    prompt = [2, 5, 9, 13, 7, 3, 11]

    def run(kernel):
        _, _, eng = _engine(cfg, temperature=0.8, seed=3,
                            decode_kernel=kernel)
        blocks, lg, ssm = eng.prefill(prompt)
        h = eng.spawn_branch(0, blocks, lg, ssm, len(prompt),
                             prompt_tokens=prompt)
        for _ in range(2):
            eng.decode_step()
        c1 = eng.fork_branch(h)          # mid-page fork
        for _ in range(2):
            eng.decode_step()
        c2 = eng.fork_branch(c1)
        assert c1 is not None and c2 is not None
        # the fork group is real: siblings share their leading page
        assert h.blocks.pages[0] == c1.blocks.pages[0] == c2.blocks.pages[0]
        for _ in range(6):
            eng.decode_step()
        state = _branch_state(eng, [h, c1, c2])
        for b in (h, c1, c2):
            eng.free_branch(b)
        eng.release_prefix(blocks)
        assert eng.allocator.used_pages == 0
        return state

    for (tok_p, pg_p, kv_p, ssm_p), (tok_t, pg_t, kv_t, ssm_t) in zip(
            run("paged"), run("tree")):
        assert tok_p == tok_t
        assert pg_p == pg_t
        if kv_p is not None:
            np.testing.assert_array_equal(kv_p[0], kv_t[0])
            np.testing.assert_array_equal(kv_p[1], kv_t[1])
        if ssm_p is not None:
            np.testing.assert_array_equal(ssm_p[0], ssm_t[0])
            np.testing.assert_array_equal(ssm_p[1], ssm_t[1])


def test_tree_decode_requires_fused_mixed_step():
    cfg = tiny_config()
    with pytest.raises(ValueError, match="decode_kernel='tree'"):
        _engine(cfg, decode_kernel="tree", mixed_step_kernel="decode")
    with pytest.raises(AssertionError):
        _engine(cfg, decode_kernel="cascade")


def test_generated_prefix_resample_admits_warm():
    """Resample-after-completion: a finished branch inserts its generated
    full pages keyed by prompt + generated tokens; re-admitting that
    trajectory (plus a fresh tail) serves the WHOLE generated prefix from
    cache — cached_tokens past the prompt, the very same page ids
    resurrected off the LRU, and zero K/V writes for the shared tokens
    (the chunk lane starts at the cached boundary)."""
    cfg = tiny_config()
    _, _, eng = _engine(cfg, temperature=0.0, prefix_cache=True)
    prompt = [2, 5, 9, 13, 7, 3, 11, 4]          # 2 full pages (ps=4)
    blocks, lg, ssm = eng.prefill(prompt)
    h = eng.spawn_branch(0, blocks, lg, ssm, len(prompt),
                         prompt_tokens=prompt)
    for _ in range(10):
        eng.decode_step()
    gen = list(h.tokens)
    written = h.blocks.length - len(prompt)      # last sample not written
    assert written == len(gen) - 1
    branch_pages = list(h.blocks.pages)
    eng.free_branch(h)                           # inserts prompt+generated
    eng.release_prefix(blocks)
    assert eng.allocator.used_pages == 0
    stats = eng.prefix_cache.stats()
    assert stats["tracked_pages"] * eng.cfg.page_size \
        >= (len(prompt) + written) // eng.cfg.page_size * eng.cfg.page_size

    resample = prompt + gen[:written] + [95]     # warm resample + new tail
    probe = eng.match_cached_tokens(resample)
    assert probe > len(prompt), "generated prefix not probeable"
    res_before = stats["resurrections"]
    st = eng.begin_prefill(resample)
    # the cached span covers the generated prefix, page-aligned and
    # capped so the last token recomputes
    cap = (len(resample) - 1) // eng.cfg.page_size * eng.cfg.page_size
    assert st.cached_tokens == cap > len(prompt)
    assert st.next_pos == st.cached_tokens       # 0 K/V bytes for the span
    n_cached = st.cached_tokens // eng.cfg.page_size
    # identical page ids: resurrected K/V, never recomputed or rewritten
    assert st.blocks.pages[:n_cached] == branch_pages[:n_cached]
    assert eng.prefix_cache.stats()["resurrections"] - res_before \
        == n_cached
    while not st.done:
        eng.decode_step()
    b2, lg2, _ = eng.finish_prefill(st)

    # a cold engine prefilling the same resample prompt. The warm span's
    # K/V was written by single-token decode steps and the cold one by
    # chunked prefill: different f32 matmul shapes round differently
    # (last-ulp K/V differences, up to ~1e-6 in logits), so the logits
    # agree to f32 rounding rather than bit for bit
    _, _, cold = _engine(cfg, temperature=0.0, prefix_cache=False)
    bc, lgc, _ = cold.prefill(resample)
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lgc),
                               rtol=1e-5, atol=1e-5)
    k2, v2 = _gather_prefix(eng, b2, len(resample))
    kc, vc = _gather_prefix(cold, bc, len(resample))
    np.testing.assert_allclose(k2, kc, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(v2, vc, rtol=1e-4, atol=1e-5)
    eng.release_prefix(b2)
    cold.release_prefix(bc)
    eng.allocator.check_invariants()


def test_generated_prefix_ssm_snapshot_gate():
    """hybrid generated-prefix reuse is gated on boundary SSM snapshots:
    decode-time insertion snapshots (conv, ssd) at every page-aligned
    boundary, so a warm resample seeds the recurrence from the deepest
    generated boundary — and stripping that snapshot (white-box) truncates
    the match to the next-shallower seedable boundary, never serving
    attention K/V the recurrence cannot resume behind."""
    cfg = tiny_config(**FAMILIES["hybrid"])
    _, _, eng = _engine(cfg, temperature=0.0, prefix_cache=True)
    prompt = [2, 5, 9, 13, 7, 3, 11, 4]          # 2 pages, chunk boundary
    blocks, lg, ssm = eng.prefill(prompt)
    h = eng.spawn_branch(0, blocks, lg, ssm, len(prompt),
                         prompt_tokens=prompt)
    for _ in range(10):
        eng.decode_step()
    gen = list(h.tokens)
    written = h.blocks.length - len(prompt)
    eng.free_branch(h)
    eng.release_prefix(blocks)

    resample = prompt + gen[:written] + [95]
    cache = eng.prefix_cache
    m_full = eng.match_cached_tokens(resample)
    assert m_full > len(prompt), "generated boundary snapshot not seedable"
    # white-box: strip the deepest snapshot-bearing node; the gate must
    # retreat to the next boundary that can still seed (conv, ssd)
    seeded = [n for n in cache._by_page.values() if n.ssm_state is not None]
    deepest = max(seeded, key=lambda n: n.depth)
    assert deepest.depth * eng.cfg.page_size == m_full
    deepest.ssm_state = None
    m_stripped = eng.match_cached_tokens(resample)
    assert m_stripped < m_full
    remaining = [n.depth for n in cache._by_page.values()
                 if n.ssm_state is not None
                 and n.depth * eng.cfg.page_size <= m_full]
    assert m_stripped == max(remaining, default=0) * eng.cfg.page_size
    # a real admission under the stripped cache still matches a cold
    # prefill (decode-time snapshots carry the step recurrence's fp32
    # rounding, so this is allclose, not array_equal)
    st = eng.begin_prefill(resample)
    assert st.cached_tokens == m_stripped
    while not st.done:
        eng.decode_step()
    b2, lg2, _ = eng.finish_prefill(st)
    _, _, cold = _engine(cfg, temperature=0.0, prefix_cache=False)
    bc, lgc, _ = cold.prefill(resample)
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lgc),
                               rtol=1e-4, atol=1e-4)
    eng.release_prefix(b2)
    cold.release_prefix(bc)
    eng.allocator.check_invariants()
