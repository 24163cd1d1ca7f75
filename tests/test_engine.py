"""Serving engine integration: the paged decode path must reproduce the
dense-model generation token-for-token (greedy), prefix sharing must be
exact, and slot/page bookkeeping must never leak."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import Model
from repro.serving import Engine, EngineConfig, SamplingParams

from conftest import tiny_config


def _engine(cfg, temperature=0.0, slots=4, seed=0):
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    eng = Engine(model, params, EngineConfig(
        page_size=4, num_pages=128, max_slots=slots,
        max_pages_per_branch=24, eos_id=1,
        sampling=SamplingParams(temperature=temperature), seed=seed))
    return model, params, eng


def _greedy_reference(model, params, prompt, steps):
    """Dense-cache greedy generation as ground truth."""
    toks = jnp.asarray(prompt)[None]
    lg, cache = model.prefill(params, tokens=toks, max_len=96)
    out = []
    cur = int(jnp.argmax(lg[0]))
    pos = len(prompt)
    for _ in range(steps):
        out.append(cur)
        lg2, cache, _ = model.decode_step(params, jnp.array([cur]), cache,
                                          jnp.array([pos]))
        cur = int(jnp.argmax(lg2[0]))
        pos += 1
    return out


@pytest.mark.parametrize("family_kw", [
    dict(),                                                     # dense
    dict(arch_type="ssm", d_ff=0, ssm_state=16, ssm_head_dim=32,
         ssm_chunk=8),
    dict(arch_type="hybrid", ssm_state=16, ssm_head_dim=32, ssm_chunk=8),
])
def test_paged_decode_matches_dense_greedy(family_kw):
    cfg = tiny_config(**family_kw)
    model, params, eng = _engine(cfg, temperature=0.0)
    prompt = [2, 5, 9, 13, 7, 3, 11]        # crosses a page boundary (ps=4)
    steps = 10
    ref = _greedy_reference(model, params, prompt, steps)

    blocks, logits, ssm = eng.prefill(prompt)
    h = eng.spawn_branch(0, blocks, logits, ssm, len(prompt))
    assert h is not None
    assert h.tokens[0] == ref[0], "first sampled token mismatch"
    for _ in range(steps - 1):
        eng.decode_step()
    assert h.tokens[:steps] == ref, f"{cfg.arch_type}: paged != dense"
    eng.free_branch(h)
    eng.release_prefix(blocks)
    assert eng.allocator.used_pages == 0


def test_sibling_branches_greedy_identical():
    """With temperature 0 all forks of one prefix generate identically —
    the shared-prefix pages and CoW bookkeeping must be bit-exact."""
    cfg = tiny_config()
    model, params, eng = _engine(cfg, temperature=0.0)
    prompt = [2, 5, 9]                       # partial page -> CoW on fork
    blocks, logits, ssm = eng.prefill(prompt)
    hs = [eng.spawn_branch(0, blocks, logits, ssm, len(prompt))
          for _ in range(3)]
    for _ in range(8):
        eng.decode_step()
    assert hs[0].tokens == hs[1].tokens == hs[2].tokens
    for h in hs:
        eng.free_branch(h)
    eng.release_prefix(blocks)
    assert eng.allocator.used_pages == 0


def test_stochastic_branches_diverge():
    cfg = tiny_config()
    model, params, eng = _engine(cfg, temperature=1.5, seed=3)
    prompt = [2, 5, 9, 4]
    blocks, logits, ssm = eng.prefill(prompt)
    hs = [eng.spawn_branch(0, blocks, logits, ssm, len(prompt))
          for _ in range(4)]
    for _ in range(12):
        eng.decode_step()
    seqs = {tuple(h.tokens) for h in hs}
    assert len(seqs) > 1, "temperature sampling should diverge branches"


def test_slot_reuse_after_free():
    cfg = tiny_config()
    model, params, eng = _engine(cfg, slots=2)
    b1, l1, s1 = eng.prefill([2, 3, 4])
    h1 = eng.spawn_branch(0, b1, l1, s1, 3)
    h2 = eng.spawn_branch(0, b1, l1, s1, 3)
    assert eng.spawn_branch(0, b1, l1, s1, 3) is None  # full
    eng.free_branch(h1)
    h3 = eng.spawn_branch(1, b1, l1, s1, 3)
    assert h3 is not None and h3.slot == h1.slot
    eng.free_branch(h2)
    eng.free_branch(h3)
    eng.release_prefix(b1)
    assert eng.allocator.used_pages == 0


def test_fork_branch_continues_context():
    """Mid-generation fork (Rebase): child's greedy continuation equals
    the parent's (same context, greedy)."""
    cfg = tiny_config()
    model, params, eng = _engine(cfg, temperature=0.0)
    prompt = [2, 5, 9, 13]
    blocks, logits, ssm = eng.prefill(prompt)
    parent = eng.spawn_branch(0, blocks, logits, ssm, len(prompt))
    for _ in range(5):
        eng.decode_step()
    child = eng.fork_branch(parent)
    assert child.tokens == parent.tokens
    for _ in range(5):
        eng.decode_step()
    assert child.tokens == parent.tokens     # greedy => identical futures
    for h in (parent, child):
        eng.free_branch(h)
    eng.release_prefix(blocks)
    assert eng.allocator.used_pages == 0


def test_live_tokens_accounting():
    cfg = tiny_config()
    model, params, eng = _engine(cfg)
    b1, l1, s1 = eng.prefill([2, 3, 4, 5, 6])
    h = eng.spawn_branch(0, b1, l1, s1, 5)
    assert eng.live_tokens() == 5
    eng.decode_step()
    assert eng.live_tokens() == 6
    eng.free_branch(h)
    assert eng.live_tokens() == 0
    eng.release_prefix(b1)


def test_cow_arrays_reuses_sentinel_pair_when_no_cow():
    """The common no-CoW step must reuse one cached (src, dst) sentinel
    pair instead of re-staging two host arrays per decode step; real CoW
    steps still build fresh index arrays."""
    _, _, eng = _engine(tiny_config())
    s1 = eng._cow_arrays([])
    s2 = eng._cow_arrays([])
    assert s1[0] is s2[0] and s1[1] is s2[1]
    assert int(s1[0][0]) == eng.cfg.num_pages    # OOB sentinel everywhere
    real = eng._cow_arrays([(3, 7)])
    assert real[0] is not s1[0]
    assert int(real[0][0]) == 3 and int(real[1][0]) == 7
    assert int(real[0][1]) == eng.cfg.num_pages  # tail stays sentinel
    # and the cached pair was not clobbered by the real-CoW call
    again = eng._cow_arrays([])
    assert again[0] is s1[0] and int(again[0][0]) == eng.cfg.num_pages


def test_attention_block_counters():
    """``attn_blocks_run`` counts the compute blocks the decode kernel runs
    over the slots, each attending its length plus the new token (an empty
    slot one block); ``attn_blocks_grid`` what walking every whole table
    would run. A block is 512 tokens, two to a 40-page table of 16."""
    model = Model(tiny_config(d_model=256, num_heads=2, num_kv_heads=1))
    params = model.init_params(jax.random.PRNGKey(0))
    eng = Engine(model, params, EngineConfig(
        page_size=16, num_pages=96, max_slots=3, max_pages_per_branch=40,
        eos_id=1, sampling=SamplingParams(temperature=0.0)))
    for prompt in ([2] * 511, [3] * 10):
        blocks, logits, ssm = eng.prefill(prompt)
        eng.spawn_branch(0, blocks, logits, ssm, len(prompt))
    run = []
    for _ in range(3):
        before = eng.attn_blocks_run
        eng.decode_step()
        run.append(eng.attn_blocks_run - before)
    # 512, 513, 514 tokens: 1, 2, 2 blocks; 11-13 tokens: 1; empty slot: 1
    assert run == [3, 4, 4]
    assert eng.attn_blocks_grid == 3 * 3 * 2
