"""stepcheck: trace-level verifier tests — negative controls (seeded
violations MUST be caught), grid exhaustiveness, manifest ratchet
semantics, the engine-enumeration drift gate, the PRM dtype-equivalence
regression pinned by the STEP005 triage, and CLI exit codes.

The bounds verifier doubles as a test harness here: tests hand it
deliberately broken ``KernelGrid``s (an un-clamped index map) and assert
the exact failure is reported — proof the checker checks, not just that
it runs.
"""
import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:          # tools/ lives at the repo root
    sys.path.insert(0, str(REPO))

from tools.stepcheck import RULES                                # noqa: E402
from tools.stepcheck import bounds, manifest                     # noqa: E402
from tools.stepcheck.bounds import (ScalarCase,                  # noqa: E402
                                    grid_exhaustive_points,
                                    verify_kernel_grid)

from conftest import tiny_config                                 # noqa: E402


# ------------------------------------------------------------ rule catalog
def test_rule_catalog_complete():
    assert sorted(RULES) == [f"STEP00{i}" for i in range(1, 8)]
    for code, (name, summary) in RULES.items():
        assert name and summary


# ----------------------------------------------- STEP007 negative controls
def _unclamp(kg, names, index_map):
    """Replace the index map of the named mappings — seed a violation."""
    return dataclasses.replace(kg, in_mappings=tuple(
        dataclasses.replace(m, index_map=index_map)
        if m.name in names else m for m in kg.in_mappings))


def test_unclamped_decode_kv_map_is_caught():
    """REMOVE flash-decode's sentinel clamp: the ragged-lengths case must
    produce STEP007 out-of-bounds findings on the exact KV mappings —
    and the shipped (clamped) map must stay silent on the same cases."""
    from repro.kernels import paged_attention_grid
    num_pages, page_size, pps = 16, 4, 5
    kg = paged_attention_grid(3, 4, 8, 2, num_pages, page_size, pps)
    cases = bounds.paged_attention_cases(num_pages, page_size, pps, 3)
    assert verify_kernel_grid(kg, cases) == []

    broken = _unclamp(kg, ("k_pages", "v_pages"),
                      lambda b, i, bt, ln: (0, bt[b, i], 0, 0))
    caught = verify_kernel_grid(broken, cases)
    assert {f.rule for f in caught} == {"STEP007"}
    assert {f.symbol for f in caught} == {"k_pages", "v_pages"}
    assert all(f.path == "paged_attention" for f in caught)


def test_unclamped_prefill_sentinel_chase_is_caught():
    """The fused prefill kernel's KV map chases ``bt[ki]`` — without the
    horizon + num_pages-1 clamps the all-sentinel table addresses page
    ``num_pages`` (one past the end)."""
    from repro.kernels import paged_prefill_grid
    num_pages, page_size, pps, t = 16, 4, 6, 8
    kg = paged_prefill_grid(t, 4, 8, 2, num_pages, page_size, pps,
                            block_q=4)
    cases = bounds.paged_prefill_cases(num_pages, page_size, pps, t)
    assert verify_kernel_grid(kg, cases) == []

    broken = _unclamp(kg, ("k_pages", "v_pages"),
                      lambda h, qi, ki, bt, info: (h, bt[ki], 0, 0))
    caught = verify_kernel_grid(broken, cases)
    assert {f.symbol for f in caught if f.rule == "STEP007"} == \
        {"k_pages", "v_pages"}
    # the sentinel chase specifically: only the num_pages-1 clamp keeps
    # an all-sentinel table in bounds
    sentinel = [c for c in cases if c.name == "all-sentinel"]
    caught = verify_kernel_grid(broken, sentinel)
    assert any(f.rule == "STEP007" and "all-sentinel" in f.message
               for f in caught)


def test_block_shape_overrun_is_caught():
    """A block that simply overhangs the array (no scalar refs at all)
    is the plain half of the containment proof."""
    from repro.kernels.introspect import BlockMapping, KernelGrid
    kg = KernelGrid(kernel="toy", grid=(3,), in_mappings=(
        BlockMapping(name="x", array_shape=(10,), block_shape=(4,),
                     index_map=lambda i: (i,)),), out_mappings=())
    caught = verify_kernel_grid(kg)
    assert len(caught) == 1 and "grid point (2,)" in caught[0].message


def test_findings_capped_per_mapping():
    from repro.kernels.introspect import BlockMapping, KernelGrid
    kg = KernelGrid(kernel="toy", grid=(100,), in_mappings=(
        BlockMapping(name="x", array_shape=(1,), block_shape=(1,),
                     index_map=lambda i: (i + 1,)),), out_mappings=())
    assert len(verify_kernel_grid(kg, max_findings_per_mapping=3)) == 3


# ------------------------------------------------------ grid exhaustiveness
def test_lattice_grids_are_exhaustive_and_pinned():
    """Pin the grid shapes the lattice sweeps so it cannot silently stop
    covering grid points (e.g. a refactor collapsing a grid axis)."""
    from repro.kernels import (flash_prefill_grid, paged_attention_grid,
                               paged_prefill_grid, ssd_scan_grid)
    kg = paged_attention_grid(3, 4, 8, 2, 16, 4, 6)
    assert kg.grid == (3,) and kg.in_mappings[1].loop == (6,)
    assert grid_exhaustive_points(kg) == 18
    # 4-page blocks over a 10-entry table: the loop spans three blocks
    kg = paged_attention_grid(3, 4, 8, 2, 16, 128, 10)
    assert kg.in_mappings[2].loop == (12,)
    assert grid_exhaustive_points(kg) == 36
    kg = paged_prefill_grid(8, 4, 8, 2, 16, 4, 6, block_q=4)
    assert kg.grid == (2, 2, 6) and grid_exhaustive_points(kg) == 24
    kg = flash_prefill_grid(2, 12, 4, 8, 2, block_q=8, block_k=8)
    assert kg.grid == (2, 4, 2, 2)      # s=12 pads to 16: 2 q/k blocks
    kg = ssd_scan_grid(2, 16, 2, 8, 4, 8)
    assert kg.grid == (2, 2, 2)


def test_lattice_covers_all_kernels_and_head_regimes():
    pairs = bounds.engine_lattice()
    assert sorted({kg.kernel for kg, _ in pairs}) == [
        "flash_prefill", "paged_attention", "paged_flash_prefill",
        "paged_tree_branch", "paged_tree_shared", "ssd_scan"]
    # MQA / GQA / MHA over 4 query heads for the attention kernels
    for kernel in ("paged_attention", "paged_tree_branch"):
        kv_counts = {kg.in_mappings[1].array_shape[0]
                     for kg, _ in pairs if kg.kernel == kernel}
        assert kv_counts == {1, 2, 4}, kernel
    assert len(pairs) == 23
    for kg, cases in pairs:
        assert grid_exhaustive_points(kg) > 0 and cases


def test_repo_kernels_prove_in_bounds():
    assert bounds.run_bounds_lattice() == []


# ------------------------------------------------------- manifest semantics
def _sigs(**kw):
    return {name: {"sig": sig, "out": []} for name, sig in kw.items()}


def test_check_manifest_missing_file_is_a_finding():
    fs = manifest.check_manifest({"engine[t]": _sigs(decode="aa")}, {})
    assert [(f.rule, f.symbol) for f in fs] == [("STEP002", "<missing>")]


def test_check_manifest_ratchets_both_directions():
    traced = {"engine[t]": _sigs(decode="aa", **{"mixed:b8xl1": "bb"})}
    committed = {"targets": {"engine[t]": _sigs(
        decode="XX", **{"mixed:b8xl2": "cc"})}}
    fs = manifest.check_manifest(traced, committed)
    got = {(f.rule, f.symbol) for f in fs}
    assert got == {("STEP002", "decode"),        # signature changed
                   ("STEP002", "mixed:b8xl1"),   # traced, not committed
                   ("STEP002", "mixed:b8xl2")}   # committed, not traced


def test_check_manifest_clean_when_identical():
    traced = {"engine[t]": _sigs(decode="aa")}
    assert manifest.check_manifest(
        traced, {"targets": traced}) == []


def test_cache_invariance_flags_signature_drift():
    off = _sigs(decode="aa", **{"mixed:b8xl1": "bb"})
    on = _sigs(decode="aa", **{"mixed:b8xl1": "ZZ"})
    fs = manifest.check_cache_invariance(off, on, "engine[dense+cache]")
    assert [(f.rule, f.symbol) for f in fs] == [("STEP001", "mixed:b8xl1")]
    assert manifest.check_cache_invariance(off, dict(off),
                                           "engine[dense+cache]") == []


def test_sim_projection_flags_extra_shapes():
    fs = manifest.check_sim_projection(["decode", "mixed:b8xl1"],
                                       ["decode", "mixed:b8xl9"])
    assert [(f.rule, f.path) for f in fs] == [("STEP001", "simulator")]
    assert manifest.check_sim_projection(
        ["decode", "mixed:b8xl1"], ["decode"]) == []


def test_committed_manifest_matches_bound():
    """The committed file itself must respect the O(buckets × lanes)
    bound it exists to enforce."""
    committed = manifest.load_manifest()
    assert committed, "tools/stepcheck/manifest.json must be committed"
    for tname, variants in committed["targets"].items():
        mixed = [v for v in variants if v.startswith("mixed:")]
        assert len(variants) == committed["variants_per_target"]
        assert len(variants) == 1 + len(mixed) and "decode" in variants


# ----------------------------------------------- enumeration + drift gate
def _real_engine(**eng_kw):
    import jax
    from repro.models import Model
    from repro.serving import Engine, EngineConfig, SamplingParams
    cfg = tiny_config()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    base = dict(page_size=4, num_pages=128, max_slots=4,
                max_pages_per_branch=24, eos_id=1,
                sampling=SamplingParams(temperature=0.0), seed=0,
                prefill_chunk=8)
    base.update(eng_kw)
    return Engine(model, params, EngineConfig(**base))


def test_step_variants_enumeration_matches_bound():
    eng = _real_engine(step_token_budget=16)
    names = [v.name for v in eng.step_variants()]
    expected = {"decode"} | {f"mixed:b{b}xl{n}"
                             for b in eng._buckets
                             for n in eng._lane_configs}
    assert len(names) == len(set(names)) == \
        1 + len(eng._buckets) * len(eng._lane_configs)
    assert set(names) == expected


def test_decode_traces_stay_within_declared_variants():
    """Drift gate: every shape the engine ACTUALLY traces while serving
    ragged mixed traffic must be declared by ``step_variants()`` —
    enumeration drift is exactly the silent-retrace bug class."""
    eng = _real_engine(step_token_budget=16)
    declared = {v.name for v in eng.step_variants()}
    rng = np.random.default_rng(3)
    sts = [eng.begin_prefill(
        [int(t) for t in rng.integers(2, 97, size=s)])
        for s in (13, 9, 17)]
    while any(not st.done for st in sts):
        eng.decode_step()
    assert eng._buckets_used, "mixed traffic never traced a chunk shape"
    traced = {f"mixed:b{b}xl{n}" for (b, n) in eng._buckets_used}
    assert traced <= declared, f"undeclared shapes: {traced - declared}"
    assert eng.prefill_compile_count <= len(declared) - 1


# ------------------------------------------- STEP005 triage regression (#5)
def test_prm_reward_dtype_equivalence():
    """The eager ``hidden.astype(jnp.float32)`` removed from
    ``Engine._step_fn`` was redundant: the fp32 PRM head promotes a bf16
    hidden state at the matmul, bit-identically. This pins that
    equivalence so the upcast can never be 'needed back' silently."""
    import jax
    import jax.numpy as jnp
    from repro.core.prm import init_prm_head, reward_logit

    params = init_prm_head(jax.random.PRNGKey(0), d_model=64)
    assert params["w1"].dtype == jnp.float32
    hidden = jax.random.normal(jax.random.PRNGKey(1), (4, 64),
                               dtype=jnp.bfloat16)
    narrow = reward_logit(params, hidden)
    wide = reward_logit(params, hidden.astype(jnp.float32))
    assert narrow.dtype == wide.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))


def test_engine_last_hidden_stays_model_dtype():
    """The step returns hidden state in the model dtype — the fp32
    boundary lives inside the PRM head, not on the dispatch."""
    eng = _real_engine()
    assert eng._last_hidden.dtype == eng.model.dtype


# ------------------------------------------------------------ CLI contract
def _cli(*args, timeout=300):
    return subprocess.run(
        [sys.executable, "-m", "tools.stepcheck", *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout)


def test_cli_list_rules():
    res = _cli("--list-rules")
    assert res.returncode == 0
    for code in RULES:
        assert code in res.stdout


def test_cli_self_test_catches_seeded_violations():
    res = _cli("--self-test")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "self-test OK" in res.stdout


def test_cli_repo_clean_with_committed_manifest_and_baseline():
    """The acceptance gate: the committed manifest + justified baseline
    make the full run exit 0; every finding is marked baselined."""
    res = _cli()
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 new" in res.stdout


def test_cli_json_output_shape():
    res = _cli("--json")
    assert res.returncode == 0, res.stdout + res.stderr
    data = json.loads(res.stdout)
    assert data["new"] == 0 and data["total"] == len(data["findings"])
    assert all(not f["new"] for f in data["findings"])


def test_cli_tampered_manifest_fails_the_build(tmp_path):
    committed = manifest.load_manifest()
    tampered = json.loads(json.dumps(committed))
    target = next(iter(tampered["targets"]))
    tampered["targets"][target]["decode"]["sig"] = "0" * 16
    bad = tmp_path / "manifest.json"
    bad.write_text(json.dumps(tampered), encoding="utf-8")
    res = _cli("--manifest", str(bad))
    assert res.returncode == 1
    assert "STEP002" in res.stdout and "decode" in res.stdout
