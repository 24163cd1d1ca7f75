"""Serving launcher: SART (or a baseline policy) on the live engine.

    PYTHONPATH=src python -m repro.launch.serve --ckpt checkpoints/reasoner \
        --policy sart --n 8 --requests 16 --rate-gap 8
    PYTHONPATH=src python -m repro.launch.serve --model qwen2-0.5b \
        --prm head --requests 6

Runs the trained tiny reasoner behind the Algorithm-1 scheduler with the
requested policy, reports accuracy and step-latency percentiles. With no
checkpoint, falls back to an untrained model (scheduling behaviour only).
``--model`` serves a registry config (``repro.configs``) at its published
widths in bf16, with seeded random weights and a seeded PRM head.
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path
from typing import Optional

# a fixed path, so that a later run of this checkout finds what an earlier
# one compiled
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"
# KV pages of the serving engine: 0.8 GB of bf16 K+V for qwen2-0.5b. The
# step program's temporaries are ~3.3x the pool, so 16,384 pages would
# not fit a 16 GB v5e chip
NUM_PAGES = 4096


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile.

    The cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
    otherwise in ``<repo>/.jax_cache``. Returns the directory."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_COMPILE_CACHE))
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# untrained-fallback trunks per family (scheduling behaviour only): lets the
# CLI drive the ssm/hybrid serving paths — masked-dt chunked admission — end
# to end without a checkpoint
_FALLBACK_FAMILIES = {
    "dense": dict(arch_type="dense", d_ff=512),
    "ssm": dict(arch_type="ssm", d_ff=0, ssm_state=16, ssm_head_dim=32,
                ssm_chunk=16),
    "hybrid": dict(arch_type="hybrid", d_ff=512, ssm_state=16,
                   ssm_head_dim=32, ssm_chunk=16),
}


def load_reasoner(ckpt_dir: Optional[str], arch: str = "dense"):
    """Returns (model, params, prm_head_params_or_None)."""
    import jax

    from ..data import tokenizer as tk
    from ..models import Model, ModelConfig
    from ..training import load_checkpoint

    has_ckpt = ckpt_dir and os.path.exists(
        os.path.join(ckpt_dir, "config.json"))
    if arch != "dense" and has_ckpt:
        import sys
        print(f"warning: checkpoint {ckpt_dir} is dense-only; "
              f"--arch {arch} serves the untrained fallback trunk instead",
              file=sys.stderr)
    if arch == "dense" and has_ckpt:
        with open(os.path.join(ckpt_dir, "config.json")) as f:
            c = json.load(f)
        cfg = ModelConfig(
            name="tiny-reasoner", arch_type="dense",
            num_layers=c["num_layers"], d_model=c["d_model"],
            vocab_size=c["vocab_size"], num_heads=c["num_heads"],
            num_kv_heads=c["num_kv_heads"], d_ff=c["d_ff"], max_seq_len=512)
        model = Model(cfg)
        like = jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0)))
        params = load_checkpoint(os.path.join(ckpt_dir, "lm.npz"))
        prm = None
        prm_path = os.path.join(ckpt_dir, "prm.npz")
        if os.path.exists(prm_path):
            prm = load_checkpoint(prm_path)
        return model, params, prm
    cfg = ModelConfig(name=f"untrained-{arch}", num_layers=2,
                      d_model=128, vocab_size=tk.VOCAB_SIZE, num_heads=4,
                      num_kv_heads=2, max_seq_len=512,
                      **_FALLBACK_FAMILIES[arch])
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params, None


def load_registry_model(cfg, seed: int):
    """(model, params, prm_head) for a registry config at its published
    widths: bf16 weights and an fp32 PRM head, both drawn from ``seed``.
    Reads no checkpoint."""
    import jax
    import jax.numpy as jnp

    from ..core.prm import init_prm_head
    from ..models import Model

    model = Model(cfg, dtype=jnp.bfloat16)
    k_lm, k_prm = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(model.init_params)(k_lm)
    return model, params, init_prm_head(k_prm, cfg.d_model)


def make_engine(model, params, prm_head, *, max_slots: int, seed: int,
                temperature: float, mixed_step_kernel: str = "fused",
                step_token_budget: int = 0, prefix_cache: bool = False):
    """The serving engine ``serve`` drives, for the given weights."""
    from ..data import tokenizer as tk
    from ..serving import Engine, EngineConfig, SamplingParams

    return Engine(model, params, EngineConfig(
        page_size=16, num_pages=NUM_PAGES, max_slots=max_slots,
        max_pages_per_branch=32, eos_id=tk.EOS,
        sampling=SamplingParams(temperature=temperature, top_p=0.95),
        seed=seed, mixed_step_kernel=mixed_step_kernel,
        step_token_budget=step_token_budget, prefix_cache=prefix_cache),
        prm_params=prm_head)


def serve(policy: str, n: int, num_requests: int, rate_gap: int,
          ckpt: Optional[str], prm_kind: str, window: int, max_tokens: int,
          max_slots: int, seed: int, temperature: float,
          arch: str = "dense", mixed_step_kernel: str = "fused",
          step_token_budget: int = 0, prefix_cache: bool = False,
          admission_policy: str = "fifo",
          deadline: Optional[int] = None,
          fault_plan: Optional[str] = None,
          model_cfg=None) -> dict:
    """Serve ``num_requests`` synthetic problems and return the metrics.

    ``model_cfg`` (a ``ModelConfig``) serves that config through
    ``load_registry_model`` in place of the checkpoint or fallback trunk
    that ``ckpt``/``arch`` select."""
    import numpy as np

    from ..core import OraclePRM, RewardHeadPRM, Scheduler, SchedulerConfig
    from ..core.scheduler import percentile_latency
    from ..data import tasks
    from ..serving import FaultInjector, FaultPlan

    if model_cfg is not None:
        model, params, prm_head = load_registry_model(model_cfg, seed)
    else:
        model, params, prm_head = load_reasoner(ckpt, arch)
    engine = make_engine(model, params, prm_head, max_slots=max_slots,
                         seed=seed, temperature=temperature,
                         mixed_step_kernel=mixed_step_kernel,
                         step_token_budget=step_token_budget,
                         prefix_cache=prefix_cache)
    if prm_kind == "head" and prm_head is not None:
        prm = RewardHeadPRM(engine)
    else:
        prm = OraclePRM(tasks.oracle_grader, noise=0.05, seed=seed + 1)

    driven = engine
    if fault_plan:
        # seeded chaos harness: the scheduler drives the injector through
        # the identical duck-typed interface (docs/robustness.md)
        driven = FaultInjector(engine, FaultPlan.parse(fault_plan))
    sch = Scheduler(driven, prm,
                    SchedulerConfig(policy=policy, n=n, window=window,
                                    max_tokens=max_tokens,
                                    admission_policy=admission_policy),
                    answer_fn=tasks.extract_answer)
    rng = np.random.default_rng(seed + 2)
    problems = []
    for i in range(num_requests):
        prob = tasks.gen_problem(rng)
        problems.append(prob)
        arrival = i * rate_gap
        sch.submit(prob.prompt_tokens(), payload=prob, arrival=arrival,
                   deadline=(arrival + deadline
                             if deadline is not None else None))
    metrics = sch.run(max_steps=2_000_000)
    correct = sum(
        1 for r, prob in zip(metrics["requests"], problems)
        if tasks.is_correct(prob, r["answer"]))
    acc = correct / max(num_requests, 1)
    out = {
        "model": model.cfg.name, "policy": policy, "n": n, "accuracy": acc,
        "p50": percentile_latency(metrics, 50),
        "p90": percentile_latency(metrics, 90),
        "p97": percentile_latency(metrics, 97),
        "p99": percentile_latency(metrics, 99),
        "queue_p50": percentile_latency(metrics, 50, "queue"),
        "decode_steps": metrics["decode_steps"],
        "generated_tokens": engine.generated_tokens,
        "clock": metrics["clock"],
        "ttfb50": percentile_latency(metrics, 50, "ttfb"),
        # O(buckets x lane-configs) for every family (masked-dt chunk lane
        # + token-budget lane packing)
        "prefill_compile_count": engine.prefill_compile_count,
        "mixed_step_kernel": mixed_step_kernel,
        "step_token_budget": step_token_budget,
        "chunk_lane_capacity": engine.admission_capacity,
        # avg chunk lanes per mixed step: > 1 means the token budget packed
        # concurrent prefills onto single decode ticks
        "chunk_lanes_per_mixed_step": (
            engine.prefill_chunk_steps / engine.mixed_steps_executed
            if engine.mixed_steps_executed else 0.0),
        # radix prefix-cache counters (None with --prefix-cache off):
        # hit_rate > 0 under shared-header workloads means warm admission
        # skipped those tokens' chunk compute and K/V writes entirely
        "prefix_cache": engine.prefix_cache_stats(),
        # admission ordering + SLO attainment (deadline_met fraction among
        # requests carrying a --deadline; None without deadlines)
        "admission_policy": metrics["admission_policy"],
        "slo": metrics["slo"],
        "completed_requests": metrics["completed_requests"],
        "unfinished_requests": metrics["unfinished_requests"],
        # failure-domain counters (quarantine/retry/restart/recovered) +
        # the injector's tallies when --fault-plan drives chaos
        "faults": metrics["faults"],
    }
    return out


def main():
    from ..configs import REGISTRY, get_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="sart",
                    choices=["vanilla", "sc", "sart", "sart_noprune",
                             "rebase"])
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate-gap", type=int, default=8,
                    help="decode steps between arrivals")
    ap.add_argument("--ckpt", default="checkpoints/reasoner")
    ap.add_argument("--model", default=None, choices=sorted(REGISTRY),
                    help="serve this registry config at its published "
                         "widths (bf16, seeded weights and PRM head) "
                         "instead of --ckpt / --arch")
    ap.add_argument("--arch", default="dense",
                    choices=sorted(_FALLBACK_FAMILIES),
                    help="untrained-fallback trunk family (ssm/hybrid "
                         "exercise the masked-dt chunked admission path)")
    ap.add_argument("--mixed-step-kernel", default="fused",
                    choices=["fused", "decode"],
                    help="chunk-row attention path of the mixed step: one "
                         "fused paged flash-prefill pass vs the per-token "
                         "flash-decode fallback")
    ap.add_argument("--step-token-budget", type=int, default=0,
                    help="max chunk-row tokens per mixed step, drawn from "
                         "multiple in-flight prefills (token-budget lane "
                         "scheduling); 0 = legacy one-FIFO-chunk-per-step")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix page-hash prompt prefix cache: admission "
                         "reuses cached page-aligned prefixes (shared "
                         "headers) instead of recomputing them")
    ap.add_argument("--admission-policy", default="fifo",
                    help="admission ordering over the arrived set: fifo "
                         "(legacy, bit-exact), lpm (longest cached prefix "
                         "first; pair with --prefix-cache), edf (earliest "
                         "--deadline first), priority, or compositions "
                         "like priority+lpm")
    ap.add_argument("--deadline", type=int, default=None,
                    help="per-request SLO: finish within this many decode "
                         "steps of arrival (drives edf ordering and the "
                         "slo attainment metrics)")
    ap.add_argument("--fault-plan", default=None,
                    help="seeded chaos injection, e.g. "
                         "'seed=3,step_rate=0.1,oop_rate=0.05,crash_at=50"
                         "+120,poison_token=5' (see repro.serving.FaultPlan"
                         ".parse); the run reports quarantine/retry/restart"
                         "/recovered counters under 'faults'")
    ap.add_argument("--prm", default="oracle", choices=["oracle", "head"])
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=96)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    setup_compile_cache()
    out = serve(args.policy, args.n, args.requests, args.rate_gap,
                args.ckpt, args.prm, args.window, args.max_tokens,
                args.slots, args.seed, args.temperature, args.arch,
                args.mixed_step_kernel, args.step_token_budget,
                args.prefix_cache, args.admission_policy, args.deadline,
                args.fault_plan,
                get_config(args.model) if args.model else None)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
