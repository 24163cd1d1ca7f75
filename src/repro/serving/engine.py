"""Continuous-batching serving engine over the paged KV cache.

The engine is the substrate Algorithm 1 (the SART scheduler) drives:

  * fixed ``max_slots`` decode batch (XLA static shapes) — a slot holds one
    *branch*; prune/complete frees the slot for the branch queue, which is
    exactly the paper's branch-granularity continuous batching;
  * prefill runs once per request; the resulting prefix pages are shared by
    all N sibling branches (ref-counted, copy-on-write on the trailing
    partial page);
  * decode steps are jit'd; host-side page accounting (boundary allocation,
    CoW) runs between steps, mirroring vLLM's CPU block manager;
  * the decode step also returns the last hidden state per slot, which feeds
    the PRM reward head with zero extra forwards (TPU adaptation: the paper
    runs a separate 7B PRM server).

On CPU the paged attention uses the vectorized jnp reference path; on TPU the
same call dispatches to the Pallas flash-decode kernel.

Public contracts (documented in docs/architecture.md and
docs/scheduling.md, which deep-link here):

  * **Admission is non-blocking**: ``begin_prefill`` reserves the prompt's
    pages up front (failing fast with ``OutOfPagesError``, allocating
    nothing) and queues a ``ChunkedPrefillState``; its chunks ride later
    ``decode_step`` calls as extra rows — one FIFO chunk per step, or up
    to ``EngineConfig.step_token_budget`` chunk-row tokens packed from
    several pending prefills as concurrent lanes (``pack_chunk_lanes``:
    oldest-first with a starvation bound).
  * **Harvested ownership**: once ``finish_prefill`` returns, the state's
    pages belong to the caller — ``abort_prefill`` on a harvested state
    only detaches it from the queue; releasing again would double-decref
    pages that live branches share.
  * **Bounded compiles**: mixed-step shapes are O(len(prefill_buckets) x
    len(chunk_lane_configs)) — all lanes of a step pad to one shared
    bucket, and lane counts round down into a small allowed set
    (``prefill_compile_count`` counts traced shapes).
  * **Inert rows never perturb state**: sentinel block-table entries drop
    K/V page writes and the ``slot_valid`` mask freezes per-slot SSM rows,
    so empty slots, suspended branches and standalone chunk draining leave
    live state bit-identical.
  * **Prefix-cache admission skips served tokens**: with
    ``EngineConfig.prefix_cache``, ``begin_prefill`` increfs the longest
    cached page-aligned prefix into the request's block list and chunks
    from the first uncached token; warm hits write zero K/V bytes and
    burn zero prefill FLOPs for shared tokens, and cache-on vs cache-off
    stays bit-exact on tokens/logits (docs/scheduling.md
    "Prefix caching").
  * **One page dispatch per step**: the step's CoW page copies and all
    lanes' chunk K/V writes execute inside the single jit'd step program
    (fused gather/scatter with OOB-sentinel padding) — no separate
    host-issued device copies, whatever the lane count.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.flash_prefill.ops import paged_flash_prefill
from ..kernels.paged_attention.ops import (paged_attention,
                                           paged_tree_attention)
from ..kernels.paged_attention.paged_attention import (block_walk,
                                                        blocks_run)
from ..kv import (BranchBlocks, OutOfPagesError, PageAllocator,
                  PrefixCache, tree_decode_map)
from ..models.attention import _project_qkv, _rotate
from ..models.config import ModelConfig
from ..models.layers import (apply_mlp, apply_norm, embed_tokens,
                             sinusoidal_embedding, unembed)
from ..models.mamba2 import (init_mamba2_state, mamba2_decode,
                             mamba2_forward)
from ..models.model import Model
from ..models.moe import apply_moe
from ..tracing import span
from .sampling import SamplingParams, sample


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    page_size: int = 16
    num_pages: int = 512
    max_slots: int = 16              # decode batch size B
    max_pages_per_branch: int = 64   # static block-table width
    max_branch_tokens: int = 512     # hard length cap per branch
    eos_id: int = 1
    sampling: SamplingParams = SamplingParams(temperature=1.0, top_p=0.95)
    seed: int = 0
    # Chunked prefill (all model families): prompts are split into
    # ``prefill_chunk``-token chunks, each padded up to one of
    # ``prefill_buckets`` and run as extra rows of the decode step, so
    # admission piggybacks on decode instead of stalling it and the number
    # of compiled prefill shapes is O(len(buckets)), not O(distinct prompt
    # lengths). ssm/hybrid chunk rows run a masked-dt scan (pad positions
    # are identity state transitions) with the running SSM state carried
    # across chunks. () derives buckets as (chunk // 2, chunk).
    chunked_prefill: bool = True
    prefill_chunk: int = 64
    prefill_buckets: tuple = ()
    # Chunk-row attention path of the mixed step. "fused" runs the chunk's
    # rows as one paged flash-prefill pass over the request's block table
    # (O(context) HBM reads per q block); "decode" is the fallback that
    # re-uses the per-token flash-decode path for every chunk row
    # (O(chunk · context) reads), kept for equivalence testing.
    mixed_step_kernel: str = "fused"
    # Decode-slot attention path. "paged" is the per-branch flash-decode
    # kernel (every branch streams its whole context, shared ancestor
    # pages once PER sibling); "tree" splits the step over a branch×page
    # dedup map built from the slots' fork topology
    # (``repro.kv.tree_decode_map``) so each shared ancestor page is
    # streamed once per step for all descendant branches and the
    # per-branch pass only covers post-fork pages. Bit-exact vs "paged"
    # (the jnp ref reconstructs identical full tables on CPU); requires
    # ``mixed_step_kernel="fused"`` — the "decode" fallback runs decode
    # slots and chunk rows through one per-branch call.
    decode_kernel: str = "paged"
    # Token-budget lane scheduling (vLLM-style): a mixed step carries up to
    # ``step_token_budget`` chunk-row tokens drawn from MULTIPLE in-flight
    # prefills (one lane per request, all lanes padded to one shared
    # bucket), instead of one FIFO chunk per step. 0 keeps the legacy
    # single-lane FIFO (bit-exact pre-lane behaviour). Must be >= the
    # largest prefill bucket when set.
    step_token_budget: int = 0
    # Allowed lane counts per mixed step. The packer rounds the number of
    # selected lanes DOWN to the nearest entry, so compiled mixed-step
    # shapes stay O(len(prefill_buckets) * len(chunk_lane_configs)).
    # () derives powers of two up to step_token_budget // max_bucket.
    chunk_lane_configs: tuple = ()
    # A pending prefill skipped by the packer (its chunk didn't fit the
    # remaining budget) more than this many times blocks packing past it:
    # no younger request overtakes it again; it then waits only on older
    # requests draining (oldest-first, bounded overtaking).
    prefill_starvation_bound: int = 4
    # Radix page-hash prompt prefix cache (docs/scheduling.md "Prefix
    # caching"): admission looks up the longest cached page-aligned prefix
    # of the prompt, increfs those pages into the request's BranchBlocks
    # and starts chunking at the first uncached token — warm hits skip
    # both the prefill compute and the K/V page writes for shared tokens
    # (few-shot headers, shared system prompts). Refcount-0 cached pages
    # park on an LRU free-list and are evicted only under page pressure.
    # Off by default: enabling changes admission *timing* (fewer chunk
    # steps on hits), though tokens/logits stay bit-exact.
    prefix_cache: bool = False


@dataclasses.dataclass(eq=False)    # identity equality: the admission
# queue holds several states at once and `in`/`remove` must never confuse
# two requests that happen to share a prompt
class ChunkedPrefillState:
    """A partially-prefilled request: pages fill chunk-by-chunk while the
    decode batch keeps stepping. ``done`` flips once the final chunk has
    been written and the last-position logits are available for
    ``spawn_branch``. For ssm/hybrid configs ``ssm_state`` carries the
    running per-layer (conv, ssd) state between chunks; it ends up holding
    exactly what the exact-length path returns. ``harvested`` flips in
    ``finish_prefill`` — from then on the pages belong to the caller and
    ``abort_prefill`` must not release them. With the prefix cache,
    ``next_pos`` starts at the cached page-aligned boundary
    (``cached_tokens``) and ``ssm_snaps`` collects (conv, ssd) snapshots
    at page-aligned chunk boundaries for cache insertion."""
    prompt: List[int]
    blocks: BranchBlocks
    next_pos: int = 0                # prompt tokens written so far
    last_logits: object = None
    ssm_state: object = None         # [L,1,...] (conv, ssd) running state
    done: bool = False
    harvested: bool = False
    passed_over: int = 0             # consecutive packer skips (starvation)
    cached_tokens: int = 0           # prefix tokens served from the cache
    ssm_snaps: Optional[dict] = None  # {token boundary: (conv, ssd)}

    @property
    def remaining(self) -> int:
        """Prompt tokens still to prefill (0 once the state is done)."""
        return len(self.prompt) - self.next_pos


def pack_chunk_lanes(pending: List[ChunkedPrefillState], *, budget: int,
                     chunk_bucket: Callable[[ChunkedPrefillState], int],
                     lane_configs: Sequence[int], starvation_bound: int):
    """Select which pending prefills contribute a chunk lane to the next
    mixed step (shared by ``Engine`` and ``SimEngine``).

    Token-budget packing: walk the admission queue oldest-first, adding one
    lane per request while the padded row count — ``shared_bucket x
    n_lanes`` — fits ``budget``. All selected lanes pad to ONE shared
    bucket (the max any selected chunk needs) and the lane count is
    rounded down to the nearest entry of ``lane_configs``, so the compiled
    mixed-step shapes stay O(buckets x lane-configs) instead of exploding
    over bucket mixtures.

    A request whose chunk would overflow the remaining budget is skipped —
    later, smaller tail chunks may still fit — but each skip increments its
    ``passed_over`` counter, and once that reaches ``starvation_bound`` the
    packer refuses to pack anything *behind* it in the queue. The
    guarantee is an ordering bound, not a latency one: no younger request
    ever overtakes a starved one, so from then on it waits only on
    requests older than itself draining (oldest-first with a bounded
    overtaking window).

    ``budget <= 0`` is the legacy single-lane FIFO: exactly one chunk — the
    oldest — per step, padded to its own bucket.

    Returns ``(selected, bucket)``: the states whose next chunk rides this
    step, in queue order, and the shared bucket each lane pads to.
    """
    if not pending:
        return [], 0
    if budget <= 0:
        st = pending[0]
        st.passed_over = 0
        return [st], chunk_bucket(st)
    max_lanes = max(lane_configs)
    selected: List[ChunkedPrefillState] = []
    shared = 0
    for st in pending:
        if len(selected) == max_lanes:
            break
        b = max(shared, chunk_bucket(st))
        if b * (len(selected) + 1) <= budget:
            selected.append(st)
            shared = b
        elif st.passed_over >= starvation_bound:
            break                     # nothing may overtake a starved lane
        else:
            st.passed_over += 1
    n = max((c for c in lane_configs if c <= len(selected)), default=0)
    for st in selected[n:]:           # rounded off this step: counts as a skip
        st.passed_over += 1
    selected = selected[:n]
    for st in selected:
        st.passed_over = 0
    bucket = max((chunk_bucket(st) for st in selected), default=0)
    return selected, bucket


def derive_lane_configs(configs: Sequence[int], budget: int,
                        max_bucket: int) -> tuple:
    """Resolve the allowed per-step lane counts for a token budget.

    Explicit ``configs`` are validated (must contain 1 — the packer rounds
    lane counts down, so some entry must always be reachable). The default
    is powers of two up to ``budget // max_bucket`` plus that maximum
    itself, keeping the set O(log(budget / bucket)) small.
    """
    if 0 < budget < max_bucket:
        raise ValueError(
            f"step_token_budget={budget} cannot carry even one full "
            f"prefill bucket of {max_bucket} tokens")
    max_lanes = max(budget // max_bucket, 1) if budget > 0 else 1
    if configs:
        lanes = tuple(sorted(set(int(c) for c in configs)))
        if not lanes or lanes[0] != 1:
            raise ValueError(
                f"chunk_lane_configs {configs} must include 1: the packer "
                "rounds lane counts down to an allowed configuration")
        if lanes[-1] > max_lanes:
            # a config the packer can never fill would make
            # admission_capacity over-reserve prompts' pages (admitted
            # requests whose chunks can't ride any step)
            raise ValueError(
                f"chunk_lane_configs {configs} exceed the "
                f"{max_lanes} lane(s) step_token_budget={budget} can "
                f"carry at bucket {max_bucket}")
        return lanes
    lanes, c = {1, max_lanes}, 1
    while c < max_lanes:
        c = min(c * 2, max_lanes)
        lanes.add(c)
    return tuple(sorted(lanes))


@dataclasses.dataclass
class BranchHandle:
    branch_id: int
    request_id: int
    slot: int
    blocks: BranchBlocks
    tokens: List[int]                # generated tokens (after the prompt)
    prompt_len: int
    done: bool = False
    last_reward: float = 0.0
    scored: bool = False              # has the PRM ever scored this branch?
    saved_ssm: object = None          # host snapshot while suspended
    # generated-prefix insertion (prefix cache on): the prompt tokens key
    # the branch's full trajectory into the radix, and page-aligned decode
    # boundaries snapshot (conv, ssd) so ssm/hybrid resamples can seed
    prompt_tokens: Optional[List[int]] = None
    ssm_snaps: Optional[dict] = None  # {token boundary: (conv, ssd)}


class StepRows(NamedTuple):
    """Host inputs of one call of the step program, as the host side
    leaves them before the upload: per row its token, position, block
    table and attended length (the ``max_slots`` decode rows first, then
    each chunk lane padded to its bucket), the decode slots' validity
    mask, each lane's valid chunk length and running SSM state (empty
    for attention-only configs), the lanes' buckets, and ``useful``: the
    active decode rows plus the valid chunk rows."""
    tokens: np.ndarray
    positions: np.ndarray
    block_tables: np.ndarray
    lengths: np.ndarray
    slot_valid: np.ndarray
    chunk_lens: np.ndarray
    chunk_state: dict
    lane_buckets: tuple
    useful: int


_NO_LANES = np.zeros((0,), np.int32)   # chunk_lens of a step with no lanes


@dataclasses.dataclass(frozen=True)
class StepVariant:
    """One reachable traced shape of ``Engine._step_fn``.

    ``name`` is ``"decode"`` for the pure-decode shape or
    ``"mixed:b{bucket}xl{lanes}"`` for a mixed step; ``lane_buckets`` is
    the static argument that selects it. ``args`` holds
    ``jax.ShapeDtypeStruct``s for the dynamic arguments *after*
    ``(params, state)`` — everything ``tools/stepcheck`` needs to trace
    the variant with ``jax.eval_shape``/``jax.make_jaxpr`` without a
    device. ``SimEngine.step_variants`` mirrors the enumeration with
    ``args=None`` (it has no step program).
    """

    name: str
    lane_buckets: Tuple[int, ...]
    args: Optional[tuple] = None


class Engine:
    def __init__(self, model: Model, params, cfg: EngineConfig,
                 prm_params: Optional[dict] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        mc = model.cfg
        if mc.uses_attention:
            assert not mc.sliding_window, \
                "paged engine serves full-attention configs; sliding-window" \
                " long-context is exercised via the dense dry-run path"
        assert cfg.mixed_step_kernel in ("fused", "decode"), \
            cfg.mixed_step_kernel
        assert cfg.decode_kernel in ("paged", "tree"), cfg.decode_kernel
        if cfg.decode_kernel == "tree" and cfg.mixed_step_kernel == "decode":
            raise ValueError(
                "decode_kernel='tree' requires mixed_step_kernel='fused' — "
                "the 'decode' fallback runs decode slots and chunk rows "
                "through one per-branch call, which the tree dedup map "
                "cannot cover (its row axis is the decode slots only)")
        self.allocator = PageAllocator(cfg.num_pages, cfg.page_size)
        self._rng = jax.random.PRNGKey(cfg.seed)
        self._next_branch_id = 0

        B, L = cfg.max_slots, mc.num_layers
        kv, hd = mc.num_kv_heads, mc.resolved_head_dim
        self.state: Dict[str, jax.Array] = {}
        if mc.uses_attention:
            shape = (L, kv, cfg.num_pages, cfg.page_size, hd)
            self.state["k_pages"] = jnp.zeros(shape, model.dtype)
            self.state["v_pages"] = jnp.zeros(shape, model.dtype)
        if mc.uses_ssm:
            conv, ssd = init_mamba2_state(mc, B, model.dtype)
            self.state["conv"] = jnp.zeros((L,) + conv.shape, model.dtype)
            self.state["ssd"] = jnp.zeros((L,) + ssd.shape, model.dtype)

        # host-side per-slot bookkeeping
        self.slots: List[Optional[BranchHandle]] = [None] * B
        self._tokens = np.zeros((B,), np.int32)
        self._positions = np.zeros((B,), np.int32)
        self._block_tables = np.full((B, cfg.max_pages_per_branch),
                                     cfg.num_pages, np.int32)  # OOB sentinel
        self._lengths = np.zeros((B,), np.int32)
        self._active = np.zeros((B,), bool)
        self._last_hidden = jnp.zeros((B, mc.d_model), model.dtype)
        self.prm_params = prm_params

        self._step_jit = jax.jit(self._step_fn,
                                 static_argnames=("lane_buckets",))
        self._prefill_cache: Dict[int, callable] = {}
        self.decode_steps_executed = 0
        # rows the step program ran (decode slots plus lane buckets) and
        # rows that carried work (active slots plus valid chunk rows): the
        # difference is padding
        self.rows_computed = 0
        self.rows_useful = 0
        # compute blocks the per-branch decode kernel runs over the decode
        # rows, and what it would run walking every row's whole table:
        # their ratio is the share of the tables the kernel walks
        self.attn_blocks_run = 0
        self.attn_blocks_grid = 0
        if mc.uses_attention:
            self._attn_walk = block_walk(
                cfg.page_size, hd, kv, self.state["k_pages"].dtype.itemsize,
                cfg.max_pages_per_branch)
        self.generated_tokens = 0         # tokens sampled by decode steps
        self.prefill_chunk_steps = 0

        # chunked prefill: every family rides the bucketed path. Attention
        # pad rows are idempotent re-writes of the last valid row; ssm/hybrid
        # pad rows get dt masked to zero (identity state transition), with
        # the running (conv, ssd) state carried on the ChunkedPrefillState.
        self._chunked_ok = cfg.chunked_prefill
        buckets = tuple(sorted(set(cfg.prefill_buckets))) or tuple(sorted(
            {max(cfg.prefill_chunk // 2, 1), cfg.prefill_chunk}))
        if buckets[-1] < cfg.prefill_chunk:
            raise ValueError(
                f"largest prefill bucket {buckets[-1]} must cover a full "
                f"prefill_chunk of {cfg.prefill_chunk} tokens — otherwise "
                "chunk rows would alias (see Engine._bucket_for)")
        self._buckets = buckets
        self._buckets_used: set = set()   # (bucket, n_lanes) shapes traced
        self._pending_prefills: List[ChunkedPrefillState] = []
        if cfg.step_token_budget > 0 and not cfg.chunked_prefill:
            raise ValueError(
                "step_token_budget requires chunked_prefill=True — "
                "synchronous exact-length admission has no chunk lanes to "
                "budget (and a capacity > 1 would let the scheduler drain "
                "its whole arrival queue in one tick)")
        self._lane_configs = derive_lane_configs(
            cfg.chunk_lane_configs, cfg.step_token_budget, buckets[-1])
        self.mixed_steps_executed = 0     # decode steps carrying >= 1 lane
        if cfg.prefix_cache and not cfg.chunked_prefill:
            raise ValueError(
                "prefix_cache requires chunked_prefill=True — the exact-"
                "length path writes every page via the dense scatter and "
                "has no chunk-start offset to resume from")
        self.prefix_cache = (PrefixCache(self.allocator)
                             if cfg.prefix_cache else None)
        # cached no-CoW (src, dst) sentinel pair (see _cow_arrays)
        self._cow_sentinel: Optional[tuple] = None
        # cached all-ungrouped tree map (see _tree_map)
        self._tree_sentinel: Optional[dict] = None

    # ------------------------------------------------------------------ util
    @property
    def free_slots(self) -> List[int]:
        """Unoccupied decode-slot indices, ascending."""
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def num_active(self) -> int:
        """Occupied decode slots (forces the host-side active mask)."""
        return int(self._active.sum())

    def live_tokens(self) -> int:
        """Total tokens currently resident in the KV pool (paper Fig. 3)."""
        return sum(s.blocks.length for s in self.slots if s is not None)

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    # --------------------------------------------------------------- prefill
    def prefill(self, prompt: List[int], exact: Optional[bool] = None):
        """Run prefill for one request to completion (synchronous
        convenience API). Returns (prefix_blocks, last_logits, ssm_state or
        None). The prefix pages are NOT yet shared — call ``spawn_branch``
        N times to fork branches off them.

        All families default to the chunked-bucketed path (same compiled
        shapes as the serving mixed step); ``exact=True`` forces the legacy
        exact-length program (one compile per distinct prompt length).
        ssm/hybrid chunks run the masked-dt scan, so right padding is an
        identity state transition rather than recurrence pollution.
        """
        if not self._chunked_ok:
            exact = True     # chunked admission disabled by config
        if not exact:
            st = self._new_chunked_state(prompt)
            while not st.done:
                self._run_step([st], self._with_lanes(
                    self._inert_rows(), [st], self._chunk_bucket(st)))
            return st.blocks, st.last_logits, st.ssm_state
        cfg, mc = self.cfg, self.model.cfg
        s = len(prompt)
        if s not in self._prefill_cache:
            self._prefill_cache[s] = self._make_prefill(s)
        run = self._prefill_cache[s]

        logits, cache = run(self.params,
                            jnp.asarray(np.asarray(prompt, np.int32))[None],
                            s)

        blocks = self._alloc_prompt_pages(s)
        ssm_state = None
        if mc.uses_attention:
            self._write_prefix_pages(cache, blocks)
        if mc.uses_ssm:
            ssm_state = (cache["conv"], cache["ssd"])  # [L,1,...]
        return blocks, logits, ssm_state

    def _check_prompt_width(self, s: int) -> None:
        assert self.allocator.pages_for(max(s, 1)) <= \
            self.cfg.max_pages_per_branch, "prompt exceeds block-table width"

    def _alloc_prompt_pages(self, s: int) -> BranchBlocks:
        self._check_prompt_width(s)
        return self.allocator.alloc_prefix(s)

    # ------------------------------------------------- chunked prefill (new)
    def _new_chunked_state(self, prompt: List[int]) -> ChunkedPrefillState:
        """Allocate a prompt's pages and, for ssm/hybrid configs, the
        per-layer running (conv, ssd) state its chunks thread through the
        mixed step. With the prefix cache, the longest cached page-aligned
        prefix is increfed into the block list and chunking starts at the
        first uncached token (ssm/hybrid reuse is gated on a cached
        boundary state to seed the recurrence); an OutOfPagesError on the
        tail allocation rolls the acquired references back, so admission
        stays all-or-nothing."""
        mc = self.model.cfg
        cached, cached_ssm = 0, None
        if self.prefix_cache is None:
            blocks = self._alloc_prompt_pages(len(prompt))
        else:
            # width check BEFORE acquire: an oversized prompt must fail
            # without acquiring references it would then leak
            self._check_prompt_width(len(prompt))
            blocks, cached_ssm = self.prefix_cache.admit(
                prompt, need_state=mc.uses_ssm)
            cached = blocks.num_shared * self.cfg.page_size
        st = ChunkedPrefillState(prompt=list(prompt), blocks=blocks,
                                 next_pos=cached, cached_tokens=cached)
        if self.prefix_cache is not None:
            st.ssm_snaps = {}
        if mc.uses_ssm:
            if cached_ssm is not None:
                st.ssm_state = cached_ssm
                st.ssm_snaps[cached] = cached_ssm
            else:
                conv, ssd = init_mamba2_state(mc, 1, self.model.dtype)
                L = mc.num_layers
                st.ssm_state = (
                    jnp.zeros((L,) + conv.shape, self.model.dtype),
                    jnp.zeros((L,) + ssd.shape, self.model.dtype))
        return st

    def begin_prefill(self, prompt: List[int]) -> ChunkedPrefillState:
        """Admit a request without stalling decode. The returned state is
        queued and its prompt chunks piggyback on subsequent ``decode_step``
        calls (one FIFO chunk per step, or up to ``step_token_budget``
        chunk-row tokens across concurrent lanes when the budget is set);
        poll ``state.done`` and harvest with ``finish_prefill``. With ``chunked_prefill=False`` the prompt
        prefills synchronously and the state returns already done. Raises
        OutOfPagesError (allocating nothing) when the KV pool cannot hold
        the prompt."""
        if not self._chunked_ok:
            blocks, logits, ssm = self.prefill(prompt, exact=True)
            return ChunkedPrefillState(
                prompt=list(prompt), blocks=blocks, next_pos=len(prompt),
                last_logits=logits, ssm_state=ssm, done=True)
        st = self._new_chunked_state(prompt)
        self._pending_prefills.append(st)
        return st

    def finish_prefill(self, st: ChunkedPrefillState):
        """Harvest a completed prefill: (prefix_blocks, last_logits, ssm).
        Ownership of the pages passes to the caller."""
        assert st.done, "prefill still has pending chunks"
        st.harvested = True
        return st.blocks, st.last_logits, st.ssm_state

    def abort_prefill(self, st: ChunkedPrefillState) -> None:
        """Drop a queued prefill and release its pages. A state already
        harvested via ``finish_prefill`` no longer owns its pages (they back
        live branches), so aborting it only detaches it from the queue —
        releasing would double-decref shared pages and corrupt refcounts."""
        if st in self._pending_prefills:
            self._pending_prefills.remove(st)
        if not st.harvested:
            self.allocator.release(st.blocks)
        st.done = True

    @property
    def has_pending_prefill(self) -> bool:
        """True while any admitted prompt still has chunks to run."""
        return bool(self._pending_prefills)

    def prefix_cache_stats(self) -> Optional[Dict]:
        """Radix-cache hit/eviction counters, or None with the cache off
        (surfaced by the serve CLI and ``Scheduler.metrics``)."""
        return (self.prefix_cache.stats()
                if self.prefix_cache is not None else None)

    def match_cached_tokens(self, prompt: List[int]) -> int:
        """Non-mutating probe for LPM admission ordering: prompt tokens a
        warm admission would serve from the radix cache right now (0 with
        the cache off). Applies the same SSM-boundary gating a real
        ``begin_prefill`` would, so the probe never over-promises."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.match_tokens(
            prompt, need_state=self.model.cfg.uses_ssm)

    @property
    def prefill_compile_count(self) -> int:
        """Distinct mixed-step chunk shapes traced so far — (bucket,
        lane-count) pairs, O(num_buckets x num_lane_configs) by
        construction (the packer pads all lanes of a step to one shared
        bucket and rounds lane counts to ``chunk_lane_configs``), vs
        O(distinct prompt lengths) for the exact path."""
        return len(self._buckets_used)

    def step_variants(self) -> List[StepVariant]:
        """Enumerate every ``_step_fn`` shape this engine can dispatch.

        Returns the pure-decode variant plus one mixed variant per
        (bucket, lane-count) pair — exactly the O(prefill_buckets ×
        chunk_lane_configs) compile bound the engine documents
        (docs/scheduling.md). The enumeration is the engine's own
        declaration of its trace surface: ``tools/stepcheck`` traces each
        variant abstractly and ratchets the signatures against its
        committed manifest, and a drift test asserts every shape
        ``decode_step`` actually traced (``_buckets_used``) is declared
        here. Each variant's ``args`` are ``ShapeDtypeStruct``s for the
        dynamic arguments after ``(params, state)``.
        """
        cfg, mc = self.cfg, self.model.cfg
        B = cfg.max_slots
        sds = jax.ShapeDtypeStruct

        def dyn(n_lanes: int, bucket: int) -> tuple:
            rows = B + n_lanes * bucket
            chunk_state: dict = {}
            if mc.uses_ssm and n_lanes:
                conv, ssd = jax.eval_shape(
                    lambda: init_mamba2_state(mc, 1, self.model.dtype))
                L = mc.num_layers
                chunk_state = {
                    "conv": sds((L, n_lanes) + conv.shape[1:], conv.dtype),
                    "ssd": sds((L, n_lanes) + ssd.shape[1:], ssd.dtype)}
            tree: dict = {}
            if cfg.decode_kernel == "tree" and mc.uses_attention:
                w = cfg.max_pages_per_branch
                tree = {"branch_bt": sds((B, w), jnp.int32),
                        "row_group": sds((B,), jnp.int32),
                        "shared_bt": sds((B, w), jnp.int32),
                        "shared_lens": sds((B,), jnp.int32)}
            return (sds((rows,), jnp.int32), sds((rows,), jnp.int32),
                    sds((rows, cfg.max_pages_per_branch), jnp.int32),
                    sds((rows,), jnp.int32),
                    sds(self._rng.shape, self._rng.dtype), chunk_state,
                    sds((n_lanes,), jnp.int32), sds((B,), jnp.bool_),
                    sds((B,), jnp.int32), sds((B,), jnp.int32), tree)

        variants = [StepVariant("decode", (), dyn(0, 0))]
        for bucket in self._buckets:
            for n in self._lane_configs:
                variants.append(StepVariant(f"mixed:b{bucket}xl{n}",
                                            (bucket,) * n, dyn(n, bucket)))
        return variants

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        # silently returning the largest bucket would alias chunk rows
        # (several prompt positions mapped onto one step row)
        raise ValueError(
            f"chunk of {n} tokens exceeds the largest prefill bucket "
            f"{self._buckets[-1]}; configure prefill_buckets to cover "
            f"prefill_chunk={self.cfg.prefill_chunk}")

    def _chunk_bucket(self, st: ChunkedPrefillState) -> int:
        return self._bucket_for(min(self.cfg.prefill_chunk, st.remaining))

    def _pack_lanes(self):
        """One packer call per decode step (it mutates the starvation
        counters of skipped states)."""
        return pack_chunk_lanes(
            self._pending_prefills, budget=self.cfg.step_token_budget,
            chunk_bucket=self._chunk_bucket,
            lane_configs=self._lane_configs,
            starvation_bound=self.cfg.prefill_starvation_bound)

    @property
    def admission_capacity(self) -> int:
        """Max prefills worth keeping in flight: the packer can serve at
        most this many chunk lanes per mixed step."""
        return self._lane_configs[-1]

    def _chunk_inputs(self, st: ChunkedPrefillState, bucket: int):
        """Build one lane's extra step rows for the next chunk of ``st``,
        padded to the step's shared ``bucket``.

        Rows past the chunk's true length shadow the last valid row (same
        token/position) so their positions/lengths stay in range, but they
        are otherwise pure padding: ``_step_fn`` drops their K/V page
        writes (``write_ok`` → OOB sentinel — from layer 2 on a pad row's
        activations can diverge from the row it shadows, so re-writing the
        same slot would clobber valid state) and the masked-dt SSM lane
        treats them as identity transitions via the lane's chunk length."""
        cfg = self.cfg
        s = len(st.prompt)
        chunk_len = min(cfg.prefill_chunk, s - st.next_pos)
        idx = np.minimum(st.next_pos + np.arange(bucket), s - 1)
        tokens = np.asarray(st.prompt, np.int32)[idx]
        row = np.full((cfg.max_pages_per_branch,), cfg.num_pages, np.int32)
        row[:len(st.blocks.pages)] = st.blocks.pages
        block_tables = np.broadcast_to(row, (bucket, row.shape[0]))
        # the step attends over lengths+1 tokens: row i covers positions
        # 0..next_pos+i inclusive, i.e. prefix + causal within the chunk
        return (tokens, idx.astype(np.int32), block_tables,
                idx.astype(np.int32), chunk_len)

    def _decode_rows(self, active: int) -> StepRows:
        """The live decode slots as a step's rows, no lanes yet."""
        return StepRows(self._tokens, self._positions, self._block_tables,
                        self._lengths, self._active, _NO_LANES, {}, (),
                        active)

    def _inert_rows(self) -> StepRows:
        """Decode rows that do nothing, for draining a prompt's chunks
        outside a decode step: sentinel block tables drop their page
        writes, and the slot-validity mask freezes the per-slot SSM
        states, so active branches are never advanced."""
        cfg = self.cfg
        B = cfg.max_slots
        return StepRows(np.zeros((B,), np.int32), np.zeros((B,), np.int32),
                        np.full((B, cfg.max_pages_per_branch),
                                cfg.num_pages, np.int32),
                        np.zeros((B,), np.int32), np.zeros((B,), bool),
                        _NO_LANES, {}, (), 0)

    def _with_lanes(self, rows: StepRows, sts: List[ChunkedPrefillState],
                    bucket: int) -> StepRows:
        """``rows`` plus one chunk of each state in ``sts`` as concurrent
        lanes, each padded to the step's shared ``bucket`` (``sts`` comes
        from ``pack_chunk_lanes``; standalone draining passes a single
        state). ssm/hybrid configs thread each lane's running per-layer
        (conv, ssd) state through the step (``chunk_*`` keys, stacked
        along a lane axis)."""
        lanes = [self._chunk_inputs(st, bucket) for st in sts]
        chunk_lens = np.asarray([ln[4] for ln in lanes], np.int32)
        chunk_state = {}
        if self.model.cfg.uses_ssm:
            chunk_state = {
                "conv": jnp.concatenate([st.ssm_state[0] for st in sts], 1),
                "ssd": jnp.concatenate([st.ssm_state[1] for st in sts], 1)}
        return StepRows(
            np.concatenate([rows.tokens] + [ln[0] for ln in lanes]),
            np.concatenate([rows.positions] + [ln[1] for ln in lanes]),
            np.concatenate([rows.block_tables] + [ln[2] for ln in lanes]),
            np.concatenate([rows.lengths] + [ln[3] for ln in lanes]),
            rows.slot_valid, chunk_lens, chunk_state, (bucket,) * len(sts),
            rows.useful + int(chunk_lens.sum()))

    def _run_step(self, sts: List[ChunkedPrefillState],
                        rows: StepRows, cow: Optional[tuple] = None,
                        tree: Optional[dict] = None):
        """Upload ``rows`` and run the step program once over them, then
        advance each state in ``sts`` (the lanes ``rows`` carries, in
        order) by its chunk. ``cow`` is the step's CoW page pairs from
        ``_cow_arrays`` (folded into the same dispatch as the chunk K/V
        writes) and ``tree`` the decode rows' dedup map; both default to
        their sentinels, for steps whose decode rows are inert.

        Each lane gets its running SSM state back advanced by exactly its
        chunk length (pad rows are identity transitions under the
        masked-dt scan). With the prefix cache, each lane snapshots its
        SSM state at page-aligned chunk boundaries and a finished prompt's
        full pages are inserted into the radix."""
        cfg, mc = self.cfg, self.model.cfg
        B = cfg.max_slots
        cow_src, cow_dst = cow if cow is not None else self._cow_arrays(())
        if tree is None:
            tree = self._tree_map()     # sentinel: decode rows are inert
        next_tokens, hidden, logits, new_state = self._step_jit(
            self.params, self.state, jnp.asarray(rows.tokens),
            jnp.asarray(rows.positions), jnp.asarray(rows.block_tables),
            jnp.asarray(rows.lengths), self._next_rng(), rows.chunk_state,
            jnp.asarray(rows.chunk_lens), jnp.asarray(rows.slot_valid),
            cow_src, cow_dst, tree, lane_buckets=rows.lane_buckets)
        new_state = dict(new_state)
        if sts and mc.uses_ssm:
            c_conv = new_state.pop("chunk_conv")      # [L, n_lanes, ...]
            c_ssd = new_state.pop("chunk_ssd")
            for i, st in enumerate(sts):
                st.ssm_state = (c_conv[:, i:i + 1], c_ssd[:, i:i + 1])
        self.state.update(new_state)
        self.rows_computed += len(rows.tokens)
        self.rows_useful += rows.useful
        if not sts:
            return next_tokens, hidden
        bucket = rows.lane_buckets[0]
        self._buckets_used.add((bucket, len(sts)))
        self.prefill_chunk_steps += len(sts)
        self.mixed_steps_executed += 1
        ps = cfg.page_size
        for i, st in enumerate(sts):
            cl = int(rows.chunk_lens[i])
            st.next_pos += cl
            if (mc.uses_ssm and st.ssm_snaps is not None
                    and st.next_pos % ps == 0):
                # a chunk boundary on a page boundary: this state can seed
                # a future request resuming at exactly next_pos tokens
                st.ssm_snaps[st.next_pos] = st.ssm_state
            if st.next_pos >= len(st.prompt):
                st.done = True
                st.last_logits = logits[B + i * bucket + cl - 1]
                if st in self._pending_prefills:
                    self._pending_prefills.remove(st)
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(st.prompt, st.blocks.pages,
                                             st.ssm_snaps)
        return next_tokens, hidden

    def _make_prefill(self, s_pad: int):
        model = self.model

        @jax.jit
        def run(params, tokens, true_len):
            positions = jnp.arange(s_pad)[None]
            # mask padding by clamping positions (outputs past true_len unused)
            logits_all, cache = _prefill_all(model, params, tokens, positions)
            logits = logits_all[0, true_len - 1]
            return logits, cache

        return run

    def _write_prefix_pages(self, cache, blocks: BranchBlocks):
        """Scatter dense prefill K/V into the allocated pages (the dense
        tensors are padded up to the page boundary; the pad region is never
        attended because block lengths mask it)."""
        ps = self.cfg.page_size
        n_pages = len(blocks.pages)
        page_ids = jnp.asarray(blocks.pages, jnp.int32)
        k = cache["k"][:, 0]                  # [L, s, kv, hd]
        v = cache["v"][:, 0]
        pad = n_pages * ps - k.shape[1]
        if pad > 0:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        self.state["k_pages"], self.state["v_pages"] = _scatter_pages(
            self.state["k_pages"], self.state["v_pages"], k, v, page_ids,
            page_size=ps)

    # --------------------------------------------------------------- branches
    def spawn_branch(self, request_id: int, prefix_blocks: BranchBlocks,
                     last_logits, ssm_state, prompt_len: int,
                     first_fork: bool = False,
                     prompt_tokens: Optional[List[int]] = None
                     ) -> Optional[BranchHandle]:
        """Fork one branch off a prefilled prefix and seat it in a free slot.

        Samples the branch's own first token from the prefill logits (the
        stochastic divergence point between siblings). Returns None if no
        slot is free (caller queues the branch). ``prompt_tokens`` (the
        request's prompt) keys the branch's generated full pages into the
        prefix cache at completion and page-aligned decode boundaries —
        without it the branch generates normally but inserts nothing.
        """
        free = self.free_slots
        if not free:
            return None
        slot = free[0]
        blocks = self.allocator.fork(prefix_blocks)
        first = int(sample(self._next_rng(), last_logits,
                           self.cfg.sampling))
        handle = BranchHandle(
            branch_id=self._next_branch_id, request_id=request_id, slot=slot,
            blocks=blocks, tokens=[first], prompt_len=prompt_len,
            prompt_tokens=(list(prompt_tokens)
                           if prompt_tokens is not None else None),
            ssm_snaps={} if self.prefix_cache is not None else None)
        self._next_branch_id += 1
        self.slots[slot] = handle

        if self.model.cfg.uses_ssm and ssm_state is not None:
            conv, ssd = ssm_state
            self.state["conv"] = self.state["conv"].at[:, slot].set(conv[:, 0])
            self.state["ssd"] = self.state["ssd"].at[:, slot].set(ssd[:, 0])

        self._seat(handle)
        return handle

    def _seat(self, h: BranchHandle):
        """Load a branch's host-side decode state into its slot row."""
        slot = h.slot
        self._tokens[slot] = h.tokens[-1]
        self._positions[slot] = h.blocks.length  # next write position
        self._refresh_block_table(h)
        self._lengths[slot] = h.blocks.length
        self._active[slot] = True

    def _refresh_block_table(self, h: BranchHandle):
        row = np.full((self.cfg.max_pages_per_branch,), self.cfg.num_pages,
                      np.int32)
        assert len(h.blocks.pages) <= self.cfg.max_pages_per_branch, \
            "branch exceeded max_pages_per_branch"
        row[:len(h.blocks.pages)] = h.blocks.pages
        self._block_tables[h.slot] = row

    def fork_branch(self, parent: BranchHandle) -> Optional[BranchHandle]:
        """Mid-generation fork (Rebase tree expansion): the child shares all
        of the parent's pages (CoW on next append) and copies its SSM state.
        Divergence comes from per-slot sampling rngs on the next step."""
        free = self.free_slots
        if not free:
            return None
        slot = free[0]
        blocks = self.allocator.fork(parent.blocks)
        handle = BranchHandle(
            branch_id=self._next_branch_id, request_id=parent.request_id,
            slot=slot, blocks=blocks, tokens=list(parent.tokens),
            prompt_len=parent.prompt_len,
            prompt_tokens=(list(parent.prompt_tokens)
                           if parent.prompt_tokens is not None else None),
            ssm_snaps=(dict(parent.ssm_snaps)
                       if parent.ssm_snaps is not None else None))
        self._next_branch_id += 1
        self.slots[slot] = handle
        if self.model.cfg.uses_ssm:
            for key in ("conv", "ssd"):
                self.state[key] = self.state[key].at[:, slot].set(
                    self.state[key][:, parent.slot])
        self._seat(handle)
        return handle

    def suspend_branch(self, h: BranchHandle) -> None:
        """Beyond-paper (the paper lists preemptible scheduling as future
        work): vacate a branch's slot while KEEPING its pages/state, so it
        can be reseated later via ``resume_branch``. SSM state is snapshot
        to host (slot rows get reused by the next occupant)."""
        assert self.slots[h.slot] is h
        if self.model.cfg.uses_ssm:
            h_saved = (np.asarray(self.state["conv"][:, h.slot]),
                       np.asarray(self.state["ssd"][:, h.slot]))
            h.saved_ssm = h_saved
        slot = h.slot
        self.slots[slot] = None
        self._active[slot] = False
        self._block_tables[slot] = self.cfg.num_pages
        self._lengths[slot] = 0
        h.slot = -1

    def resume_branch(self, h: BranchHandle) -> bool:
        """Reseat a suspended branch. Returns False when no slot is free."""
        free = self.free_slots
        if not free:
            return False
        slot = free[0]
        h.slot = slot
        self.slots[slot] = h
        if self.model.cfg.uses_ssm and getattr(h, "saved_ssm", None):
            conv, ssd = h.saved_ssm
            self.state["conv"] = self.state["conv"].at[:, slot].set(
                jnp.asarray(conv))
            self.state["ssd"] = self.state["ssd"].at[:, slot].set(
                jnp.asarray(ssd))
            h.saved_ssm = None
        self._seat(h)
        return True

    def pages_needed_for_step(self) -> int:
        """Pages the next decode step will allocate (boundary + CoW)."""
        ps = self.cfg.page_size
        need = 0
        for h in self.slots:
            if h is None:
                continue
            b = h.blocks
            if self.allocator.needs_cow(b):
                need += 1
            if b.length % ps == 0 and b.length // ps == len(b.pages):
                need += 1
        return need

    def _insert_generated(self, h: BranchHandle) -> None:
        """Insert a branch's generated full pages into the prefix cache,
        keyed by prompt + generated tokens (the trailing partial page
        keeps private CoW semantics; ``insert`` skips it). Released pages
        then park on the LRU instead of freeing, so a resample of the
        same trajectory — or any follow-up sharing the generated prefix —
        admits warm. ``ssm_snaps`` attaches (conv, ssd) snapshots to the
        page-aligned boundaries that have one, preserving the
        ``acquire(need_state=True)`` seedable-boundary gate for
        ssm/hybrid. Gated on attention: pure-ssm decode allocates no
        generated pages to insert."""
        if (self.prefix_cache is None or h.prompt_tokens is None
                or not self.model.cfg.uses_attention):
            return
        written = h.blocks.length - h.prompt_len
        if written <= 0:
            return
        key = list(h.prompt_tokens) + h.tokens[:written]
        self.prefix_cache.insert(key, h.blocks.pages, h.ssm_snaps)

    def free_branch(self, h: BranchHandle):
        """Release a branch's slot and eagerly free its pages (inserting
        its generated full pages into the prefix cache first, so they park
        warm on the LRU instead of freeing)."""
        self._insert_generated(h)
        self.allocator.release(h.blocks)
        slot = h.slot
        if slot >= 0:                 # suspended branches hold no slot
            self.slots[slot] = None
            self._active[slot] = False
            self._block_tables[slot] = self.cfg.num_pages
            self._lengths[slot] = 0
        h.done = True

    def release_prefix(self, prefix_blocks: BranchBlocks):
        """Drop the scheduler's own reference to a request's prefix."""
        self.allocator.release(prefix_blocks)

    # ----------------------------------------------------------------- decode
    def _cow_arrays(self, cows: Sequence[tuple]):
        """Pack a step's (old, new) CoW page pairs into the fixed-shape
        [max_slots] index arrays ``_step_fn`` consumes (each decode slot
        CoWs at most once per step). Unused entries hold the OOB sentinel:
        the fused gather/scatter drops them, so the pure-decode and mixed
        shapes stay identical whether or not any copy happens.

        Most steps CoW nothing, so the all-sentinel pair is built and
        transferred once and reused — no per-step host->device copy for
        the common case."""
        if not cows:
            if self._cow_sentinel is None:
                empty = np.full((self.cfg.max_slots,), self.cfg.num_pages,
                                np.int32)
                self._cow_sentinel = (jnp.asarray(empty), jnp.asarray(empty))
            return self._cow_sentinel
        src = np.full((self.cfg.max_slots,), self.cfg.num_pages, np.int32)
        dst = np.full((self.cfg.max_slots,), self.cfg.num_pages, np.int32)
        for j, (old, new) in enumerate(cows):
            src[j], dst[j] = old, new
        return jnp.asarray(src), jnp.asarray(dst)

    def _tree_map(self, blocks: Optional[List[Optional[BranchBlocks]]]
                  = None) -> dict:
        """The decode rows' branch×page dedup map for the tree kernel,
        as the ``tree`` step argument. Empty dict with the per-branch
        kernel (zero pytree leaves — the traced shapes are unchanged);
        ``blocks=None`` returns the cached all-ungrouped sentinel map
        (standalone chunk drains: every decode row is inert)."""
        cfg = self.cfg
        if cfg.decode_kernel != "tree" or not self.model.cfg.uses_attention:
            return {}
        if blocks is None:
            if self._tree_sentinel is None:
                b, w = cfg.max_slots, cfg.max_pages_per_branch
                sent = np.full((b, w), cfg.num_pages, np.int32)
                self._tree_sentinel = {
                    "branch_bt": jnp.asarray(sent),
                    "row_group": jnp.full((b,), b, jnp.int32),
                    "shared_bt": jnp.asarray(sent),
                    "shared_lens": jnp.zeros((b,), jnp.int32)}
            return self._tree_sentinel
        rg, sbt, sl, bbt = tree_decode_map(
            blocks, pages_per_branch=cfg.max_pages_per_branch,
            num_pages=cfg.num_pages, page_size=cfg.page_size)
        return {"branch_bt": jnp.asarray(bbt), "row_group": jnp.asarray(rg),
                "shared_bt": jnp.asarray(sbt),
                "shared_lens": jnp.asarray(sl)}

    def _step_fn(self, params, state, tokens, positions, block_tables,
                 lengths, rng, chunk_state, chunk_lens, slot_valid,
                 cow_src, cow_dst, tree, lane_buckets: tuple = ()):
        """One batched token step, generic in row count and lane count.

        Rows 0..max_slots-1 are the decode slots; any extra rows are the
        step's prefill chunk *lanes* — ``lane_buckets`` (static) gives the
        padded row count of each lane, ``chunk_lens[i]`` (traced) its true
        chunk length, and each lane's rows belong to one request (same
        math as decode: embed one token, write its K/V at ``positions``
        via the row's block table, attend over ``lengths``+1 tokens).
        Causality inside a chunk falls out of the length mask: all rows
        scatter K/V before attention, and row i's length covers only
        positions <= its own. The packer emits only uniform lane tuples
        with lane counts drawn from a small allowed set, so the compiled
        shapes stay O(buckets x lane-configs): the pure decode shape plus
        one mixed shape per (bucket, lane-count) pair.

        With ``mixed_step_kernel="fused"`` (the default) each lane's
        attention runs as one paged flash-prefill pass over its request's
        block table instead of per-token flash-decode calls — same masking
        semantics (row i sees absolute positions <= pos0 + i), one
        O(context) HBM stream per q block instead of one per row.
        ``"decode"`` keeps the legacy unified call for fallback and
        equivalence testing.

        The SSM mixer of ssm/hybrid configs is inherently sequential, so
        its chunk rows can't be independent like attention's: each lane
        runs as ONE [1, bucket, D] sequence through the masked-dt chunked
        scan instead, seeded by its slice of ``chunk_state`` (per-layer
        (conv, ssd) stacked along a lane axis, carried across chunks on
        each ChunkedPrefillState) with only the first ``chunk_lens[i]``
        rows valid — pad rows are exact identity transitions.
        ``slot_valid`` masks the per-slot SSM state update of decode rows
        the same way, so inert rows (standalone chunk draining, empty
        slots) never perturb suspended or future occupants.

        ``cow_src``/``cow_dst`` ([max_slots], OOB-sentinel padded) are the
        step's copy-on-write page pairs, applied as ONE fused
        gather/scatter inside this program before any K/V write — so a
        mixed step's chunk page writes and its CoW copies all ride a
        single device dispatch, however many lanes it carries (the
        batching mirror of the old host-side ``cows`` loop).

        ``tree`` is the decode rows' branch×page dedup map
        (``decode_kernel="tree"``: row_group / shared_bt / shared_lens /
        branch_bt from ``repro.kv.tree_decode_map``, built host-side from
        the slots' post-accounting fork topology) — the decode-slot
        attention then streams each fork group's shared ancestor pages
        once for all members and covers only post-fork suffixes
        per-branch. Empty dict with the per-branch kernel: zero pytree
        leaves, so that path's traced shapes are byte-identical to
        before the map existed. CoW runs before attention, so no row's
        shared page is written mid-step and the map stays sound.
        """
        model, mc, cfg = self.model, self.model.cfg, self.cfg
        B = tokens.shape[0]
        nS = cfg.max_slots
        if mc.uses_attention:
            # CoW before any write: sentinel dst rows drop (mode="drop");
            # their src gathers clamp to a resident page (explicitly — OOB
            # gather is backend-defined) and the garbage is discarded
            src = jnp.minimum(cow_src, cfg.num_pages - 1)
            state = dict(state)
            with jax.named_scope("kv_write"):
                state["k_pages"] = state["k_pages"].at[:, :, cow_dst].set(
                    state["k_pages"][:, :, src], mode="drop")
                state["v_pages"] = state["v_pages"].at[:, :, cow_dst].set(
                    state["v_pages"][:, :, src], mode="drop")
        # static: lane row offsets into the step's row axis
        lane_off = []
        off = nS
        for bk in lane_buckets:
            lane_off.append(off)
            off += bk
        # static: does this shape carry SSM chunk lanes?
        ssm_chunk_lane = bool(chunk_state) and mc.uses_ssm
        # static: chunk rows take the fused paged flash-prefill path (one
        # flash pass per lane over its request's block table) instead of
        # riding the per-token flash-decode loop — O(context) vs
        # O(chunk · context) HBM reads per layer
        fused_chunk = (B > nS and mc.uses_attention
                       and cfg.mixed_step_kernel == "fused")
        # static: decode-slot attention rides the tree dedup map (an empty
        # dict means the per-branch kernel — dict-ness is static under jit)
        tree_decode = bool(tree)
        on_tpu = jax.default_backend() == "tpu"
        x = embed_tokens(mc, params["embed"], tokens[:, None])
        if mc.pos_embedding == "sinusoidal":
            x = x + sinusoidal_embedding(positions, mc.d_model)[:, None].astype(x.dtype)

        page_of = block_tables[jnp.arange(B), positions // cfg.page_size]
        slot_in_page = positions % cfg.page_size
        if B > nS:
            # chunk rows past a lane's chunk length are pure padding: route
            # their K/V writes to the OOB sentinel (mode="drop"). Shadowing
            # the last valid row is NOT idempotent for hybrid configs —
            # from layer 2 on, pad-row inputs differ (the masked SSM lane
            # leaves unspecified values at pad positions) and would clobber
            # the valid row's K/V.
            write_ok = jnp.concatenate(
                [jnp.ones((nS,), bool)]
                + [jnp.arange(bk) < chunk_lens[i]
                   for i, bk in enumerate(lane_buckets)])
            page_of = jnp.where(write_ok, page_of, cfg.num_pages)

        def layer(carry, scanned):
            x = carry
            layer_p = scanned["p"]
            h = apply_norm(mc, layer_p["norm1"], x)
            mix = jnp.zeros_like(x)
            outs = {}
            if mc.uses_attention:
                kp, vp = scanned["k_pages"], scanned["v_pages"]
                q, k, v = _project_qkv(mc, layer_p["attn"], h)
                pos_in = positions[:, None]
                if mc.pos_embedding == "mrope":
                    pos_in = jnp.broadcast_to(pos_in[..., None], (B, 1, 3))
                q, k = _rotate(mc, q, k, pos_in)
                # write new token's k/v into pages ([kv, page, slot, hd])
                with jax.named_scope("kv_write"):
                    kp = kp.at[:, page_of, slot_in_page].set(
                        jnp.moveaxis(k[:, 0], 1, 0), mode="drop")
                    vp = vp.at[:, page_of, slot_in_page].set(
                        jnp.moveaxis(v[:, 0], 1, 0), mode="drop")

                @jax.named_scope("attn_decode")
                def slot_attention():
                    """Decode-slot attention; with the tree map, shared
                    ancestor pages stream once per fork group and
                    suffixes run per-branch (bit-exact vs the per-branch
                    call — the map decomposes the same block tables)."""
                    if tree_decode:
                        return paged_tree_attention(
                            q[:nS, 0], kp, vp, tree["row_group"],
                            tree["shared_bt"], tree["shared_lens"],
                            tree["branch_bt"], lengths[:nS] + 1,
                            use_kernel=on_tpu)
                    return paged_attention(
                        q[:nS, 0], kp, vp, block_tables[:nS],
                        lengths[:nS] + 1, use_kernel=on_tpu)

                if fused_chunk:
                    # decode rows keep the flash-decode path; each lane's
                    # rows share one block table (they are broadcast rows
                    # of the same request) and run as a single flash pass
                    # with causal masking against absolute positions —
                    # row i at pos0 + i sees the prefix plus the chunk K/V
                    # written above. Bucket-pad rows (>= the lane's chunk
                    # length) emit exact zeros; their writes were already
                    # dropped.
                    att_parts = [slot_attention()]
                    for i, bk in enumerate(lane_buckets):
                        o = lane_off[i]
                        with jax.named_scope("attn_prefill"):
                            att_parts.append(paged_flash_prefill(
                                q[o:o + bk, 0], kp, vp, block_tables[o],
                                positions[o], chunk_lens[i],
                                use_kernel=on_tpu))
                    att = jnp.concatenate(att_parts, 0)
                elif B > nS:
                    # mixed_step_kernel="decode" fallback: decode slots
                    # and chunk rows ride one per-branch call (the tree
                    # map is rejected for this combination in __init__)
                    with jax.named_scope("attn_decode"):
                        att = paged_attention(
                            q[:, 0], kp, vp, block_tables, lengths + 1,
                            use_kernel=on_tpu)
                else:
                    att = slot_attention()
                y = att.reshape(B, 1, -1) @ layer_p["attn"]["wo"]
                mix = mix + y
                outs["k_pages"], outs["v_pages"] = kp, vp
            if mc.uses_ssm:
                y, conv, ssd = mamba2_decode(
                    mc, layer_p["mamba"], h[:nS], scanned["conv"],
                    scanned["ssd"], valid=slot_valid)
                outs["conv"] = conv.astype(scanned["conv"].dtype)
                outs["ssd"] = ssd.astype(scanned["ssd"].dtype)
                if ssm_chunk_lane:
                    # all lanes run as ONE batched masked-dt scan: the
                    # packer only emits uniform lane tuples, the stacked
                    # lane-state axis is the batch axis, and mamba2_forward
                    # takes a per-row valid_len — so lane count adds no
                    # sequential trace depth
                    assert len(set(lane_buckets)) == 1, lane_buckets
                    bk = lane_buckets[0]
                    x_ch = h[nS:, 0].reshape(len(lane_buckets), bk, -1)
                    y_ch, (c_conv, c_ssd) = mamba2_forward(
                        mc, layer_p["mamba"], x_ch,
                        initial=(scanned["chunk_conv"],
                                 scanned["chunk_ssd"]),
                        valid_len=chunk_lens)
                    outs["chunk_conv"] = c_conv.astype(
                        scanned["chunk_conv"].dtype)
                    outs["chunk_ssd"] = c_ssd.astype(
                        scanned["chunk_ssd"].dtype)
                    y = jnp.concatenate(
                        [y, y_ch.reshape(B - nS, 1, -1)], 0)
                mix = mix + y
            if mc.arch_type == "hybrid":
                mix = mix * 0.5
            x = x + mix
            if mc.d_ff:
                with jax.named_scope("mlp"):
                    h2 = apply_norm(mc, layer_p["norm2"], x)
                    if mc.uses_moe:
                        y, _ = apply_moe(mc, layer_p["moe"], h2)
                    else:
                        y = apply_mlp(mc, layer_p["mlp"], h2)
                x = x + y
            return x, outs

        scanned_in = {"p": params["layers"]}
        for key in ("k_pages", "v_pages", "conv", "ssd"):
            if key in state:
                scanned_in[key] = state[key]
        if ssm_chunk_lane:
            scanned_in["chunk_conv"] = chunk_state["conv"]
            scanned_in["chunk_ssd"] = chunk_state["ssd"]
        x, new_state = jax.lax.scan(layer, x, scanned_in)
        with jax.named_scope("unembed"):
            x = apply_norm(mc, params["final_norm"], x)
            hidden = x[:, 0]
            logits = unembed(mc, params["embed"], hidden)
        with jax.named_scope("sample"):
            keys = jax.random.split(rng, B)
            next_tokens = jax.vmap(lambda r, l: sample(r, l, cfg.sampling))(
                keys, logits)
        # hidden stays in the model compute dtype: its only consumer is
        # the PRM head, whose fp32 weights promote the matmul operand at
        # the use site — an eager upcast here would ship d_model fp32
        # bytes per slot per step for numerically identical rewards
        # (pinned by test_stepcheck.test_prm_reward_dtype_equivalence)
        return next_tokens, hidden, logits, new_state

    def decode_step(self) -> Dict[int, int]:
        """One decode step for all active slots, piggybacking up to
        ``step_token_budget`` chunk-row tokens of pending prefills as
        concurrent lanes (mixed step) — one FIFO chunk when the budget is
        unset (see ``pack_chunk_lanes``).

        Handles host-side page accounting (boundary alloc + CoW) *before* the
        jit'd step, then appends the sampled token to each active branch.
        Returns {slot: new_token}.

        Traced as ``engine.step`` with four children in order (see
        ``repro.tracing``): ``engine.prepare`` (page accounting, lane
        packing, the step's host arrays), ``engine.dispatch`` (uploads and
        the step program's enqueue), ``engine.sync`` (the wait for the
        sampled tokens) and ``engine.harvest`` (the per-slot loop).
        """
        cfg = self.cfg
        if not self._active.any() and not self._pending_prefills:
            return {}
        with span("engine.step"):
            with span("engine.prepare") as prepare:
                cows = self._account_pages()
                # pack only after the page accounting above: an
                # OutOfPagesError abort must not charge skipped prefills'
                # starvation counters for a step that never ran. The
                # step's CoW copies ride the step program itself (one
                # fused gather/scatter batched with the chunk K/V writes
                # — no separate host dispatch, whatever the lane count)
                lanes, bucket = self._pack_lanes()
                # the tree dedup map reflects POST-accounting topology: CoW
                # and boundary allocation above already diverged any page
                # this step writes, so no fork group's shared span covers
                # a written page
                tree = (self._tree_map([h.blocks if h is not None else None
                                        for h in self.slots])
                        if cfg.decode_kernel == "tree" else None)
                cow = self._cow_arrays(cows)
                active = self.num_active
                rows = self._decode_rows(active)
                if lanes:
                    rows = self._with_lanes(rows, lanes, bucket)
                if self.model.cfg.uses_attention and tree is None:
                    self._count_attn_blocks()
                prepare.set_metadata(rows=active, lanes=len(lanes),
                                     bucket=bucket, cows=len(cows))
            with span("engine.dispatch"):
                next_tokens, hidden = self._run_step(lanes, rows, cow, tree)
                self._last_hidden = hidden[:cfg.max_slots]
                self.decode_steps_executed += 1
            # the one mandated sync per step: sampled tokens drive
            # host-side branch bookkeeping (EOS detection, page
            # accounting) before the next dispatch can be built
            with span("engine.sync"):
                toks = np.asarray(next_tokens)  # reprolint: disable=REP005
            with span("engine.harvest"):
                out = self._harvest(toks)
        return out

    def _count_attn_blocks(self):
        """Add this step's decode rows to ``attn_blocks_run`` and
        ``attn_blocks_grid``: each slot attends ``length + 1`` tokens (an
        empty slot one), counted by the kernel's own block rule."""
        self.attn_blocks_run += int(
            blocks_run(self._lengths + 1, *self._attn_walk).sum())
        self.attn_blocks_grid += len(self._lengths) * self._attn_walk[1]

    def _account_pages(self) -> List[tuple]:
        """Page accounting for the token each active branch is about to
        write: boundary allocation and copy-on-write. Returns the step's
        (old, new) CoW page pairs."""
        cfg, mc = self.cfg, self.model.cfg
        cows: List[tuple] = []
        if not mc.uses_attention:
            for h in self.slots:
                if h is not None:
                    h.blocks.length += 1
            return cows
        cap = cfg.max_pages_per_branch * cfg.page_size
        for h in self.slots:
            if h is not None and h.blocks.length + 1 > cap:
                # static block table full: surface as memory pressure so
                # the scheduler's evict-longest path force-completes the
                # branch instead of the table-refresh assert tripping
                raise OutOfPagesError(
                    "branch at block-table capacity "
                    f"({cap} tokens)")
        if self.pages_needed_for_step() > self.allocator.free_pages:
            raise OutOfPagesError(
                "decode step needs more pages than are free")
        # reprolint REP002 baselined: the pages_needed_for_step
        # pre-check above reserves this loop's worst case, so
        # append_token cannot raise mid-way
        for h in self.slots:
            if h is None:
                continue
            cow = self.allocator.append_token(h.blocks)
            if cow is not None:
                cows.append(cow)
            self._refresh_block_table(h)
        return cows

    def _harvest(self, toks: np.ndarray) -> Dict[int, int]:
        """Append each active branch's sampled token and advance its slot;
        page-aligned branches publish their generated pages to the prefix
        cache. Returns {slot: new_token}."""
        mc = self.model.cfg
        ps = self.cfg.page_size
        out: Dict[int, int] = {}
        for slot, h in enumerate(self.slots):
            if h is None:
                continue
            tok = int(toks[slot])
            h.tokens.append(tok)
            out[slot] = tok
            self._tokens[slot] = tok
            self._positions[slot] += 1
            self._lengths[slot] += 1
            if (self.prefix_cache is not None
                    and h.prompt_tokens is not None
                    and h.blocks.length % ps == 0):
                # page-aligned decode boundary: long-running branches
                # publish their generated full pages without waiting for
                # completion. The post-step slot state corresponds to
                # exactly blocks.length consumed tokens, so it can seed
                # an ssm/hybrid resume at this boundary.
                if mc.uses_ssm and h.ssm_snaps is not None:
                    h.ssm_snaps[h.blocks.length] = (
                        self.state["conv"][:, h.slot:h.slot + 1],
                        self.state["ssd"][:, h.slot:h.slot + 1])
                self._insert_generated(h)
        self.generated_tokens += len(out)
        return out

    # --------------------------------------------------------------- scoring
    def score_slots(self) -> np.ndarray:
        """PRM reward per slot from the cached last hidden state (traced
        as ``prm.head``)."""
        if self.prm_params is None:
            raise RuntimeError("engine has no PRM head")
        from ..core.prm import reward_from_hidden
        with span("prm.head", slots=self.cfg.max_slots):
            r = reward_from_hidden(self.prm_params, self._last_hidden)
            return np.asarray(r)


# --------------------------------------------------------------------- helpers


def _prefill_all(model: Model, params, tokens, positions):
    """Model.prefill but returning logits for all positions (for true-length
    indexing under padding)."""
    mc = model.cfg
    x = model._embed_inputs(params, tokens, None)
    b, s, _ = x.shape
    if mc.pos_embedding == "mrope" and positions.ndim == 2:
        positions = jnp.broadcast_to(positions[..., None], (b, s, 3))
    cache = model.init_cache(b, s)

    def body(x, scanned):
        layer_p, layer_cache = scanned
        return model._layer_prefill(layer_p, layer_cache, x, positions, s)

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = apply_norm(mc, params["final_norm"], x)
    logits = unembed(mc, params["embed"], x)
    return logits, new_cache


@functools.partial(jax.jit, static_argnames=("page_size",))
def _scatter_pages(k_pages, v_pages, k, v, page_ids, page_size):
    """k, v: [L, n_pages*ps, kv, hd] -> scatter into [L, kv, P, ps, hd]."""
    l, s, kvh, hd = k.shape
    n = s // page_size
    kk = k.reshape(l, n, page_size, kvh, hd).transpose(0, 3, 1, 2, 4)
    vv = v.reshape(l, n, page_size, kvh, hd).transpose(0, 3, 1, 2, 4)
    k_pages = k_pages.at[:, :, page_ids].set(kk.astype(k_pages.dtype))
    v_pages = v_pages.at[:, :, page_ids].set(vv.astype(v_pages.dtype))
    return k_pages, v_pages
