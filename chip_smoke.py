#!/usr/bin/env python3
"""Smoke run of the live SART serving path on one TPU chip.

    python3 chip_smoke.py

Serves qwen2-0.5b at its published widths (24 layers, d_model 896, 14 query
heads over 2 KV heads, vocab 151,936) in bf16 with seeded random weights,
through ``repro.launch.serve.serve``: the SART policy with N=8 branches and
the reward-head PRM, on the engine's default path (chunked prefill, fused
mixed step, paged decode). It then checks that

  * every request completed, with no step fault, restart or quarantine;
  * the compiled step programs hold the Pallas decode and paged-prefill
    kernels (``tpu_custom_call``), i.e. the CPU reference branch was not
    taken;
  * prefill and paged-decode logits agree with a float32 ``Model.forward``
    over the same tokens.

Runs in one process and starts none. Exits non-zero, printing no result,
when JAX finds no TPU. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
MODEL = "qwen2-0.5b"
SEED = 0
SERVE_ARGS = dict(policy="sart", n=8, num_requests=6, rate_gap=4, ckpt=None,
                  prm_kind="head", window=8, max_tokens=256, max_slots=16,
                  seed=SEED, temperature=0.9)
REF_DECODE_STEPS = 8
# Largest |engine - reference| logit difference allowed, as a fraction of
# the reference's largest |logit|. The engine runs bf16 weights, bf16
# activations and a bf16 KV cache through every layer; the reference runs
# the same (bf16-valued) weights in float32 at "highest" matmul precision.
# bf16 keeps 8 significant bits (relative rounding 2^-9 ~ 0.2%), and the
# roundings compound over the residual layers: at the smoke widths on the
# CPU the gap is ~1% with 2 layers and ~2% with 24, and at the published
# widths on a TPU v5e chip it was 1.55%.
LOGIT_RTOL = 0.05
# the jitted kernel wrappers whose names the compiled calls carry
KERNELS = ("paged_attention", "paged_flash_prefill")


def compile_step(engine, variant):
    """Compile one ``Engine.step_variants`` entry of the engine's jitted
    step. Returns (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = engine._step_jit.lower(
        engine.params, engine.state, *variant.args,
        lane_buckets=variant.lane_buckets).compile()
    return compiled, time.perf_counter() - t0


def pallas_kernels(compiled) -> list:
    """Names of the Pallas kernels wrapped in ``tpu_custom_call``s of a
    compiled program, one entry per call."""
    found = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            found += [k for k in KERNELS if k in line]
    return found


def logits_vs_reference(model, params, engine, prompt, steps):
    """Prefill ``prompt`` and run ``steps`` decode steps of one branch on
    ``engine`` (greedy sampling makes the run deterministic), then compare
    its logits with a float32 ``Model.forward`` over the same tokens.

    Returns (largest |difference|, largest |reference logit|)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import Model
    from repro.models.layers import unembed

    st = engine.begin_prefill(prompt)
    while not st.done:
        engine.decode_step()
    blocks, last_logits, ssm = engine.finish_prefill(st)
    h = engine.spawn_branch(0, blocks, last_logits, ssm, len(prompt))
    got = [last_logits]
    for _ in range(steps):
        engine.decode_step()
        got.append(unembed(model.cfg, params["embed"],
                           engine._last_hidden[h.slot]))
    got = np.stack([np.asarray(g, np.float32) for g in got])
    tokens = jnp.asarray([list(prompt) + h.tokens[:steps]], jnp.int32)
    engine.free_branch(h)
    engine.release_prefix(blocks)

    ref_model = Model(model.cfg, dtype=jnp.float32)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(ref_model.forward)(params32, tokens)
    # row len(prompt)-1 predicts the first generated token, like the
    # prefill logits; each later row matches one decode step
    ref = np.asarray(ref[0, len(prompt) - 1:], np.float32)
    return float(np.abs(got - ref).max()), float(np.abs(ref).max())


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import serve as srv
    except ImportError as exc:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({exc})", file=sys.stderr)
        return 2
    cache_dir = srv.setup_compile_cache()

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.data import tasks

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"chip_smoke: JAX found no device ({exc})", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    failures = []
    print(f"device: {dev.device_kind} x{len(devices)}  jax {jax.__version__}"
          f"  compile cache: {cache_dir}", flush=True)

    cfg = get_config(MODEL)
    model, params, prm_head = srv.load_registry_model(cfg, SEED)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim="
          f"{cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"dtype={model.dtype.__name__} params={n_params}", flush=True)

    # 1. the step programs serve() runs: compile (this also fills the
    # persistent cache serve() then reads) and look for the kernels
    engine = srv.make_engine(model, params, prm_head,
                             max_slots=SERVE_ARGS["max_slots"], seed=SEED,
                             temperature=SERVE_ARGS["temperature"])
    variants = engine.step_variants()
    mixed = next(v for v in variants if v.name.startswith("mixed:"))
    for variant, want in ((variants[0], KERNELS[:1]), (mixed, KERNELS)):
        compiled, secs = compile_step(engine, variant)
        found = pallas_kernels(compiled)
        print(f"compile {variant.name}: {secs:.2f} s, tpu_custom_call: "
              f"{found}", flush=True)
        missing = set(want) - set(found)
        if missing:
            failures.append(f"{variant.name} lacks the Pallas kernel(s) "
                            f"{sorted(missing)}")
    del engine, compiled
    gc.collect()

    # 2. serve through the normal entry point
    t0 = time.perf_counter()
    out = srv.serve(**SERVE_ARGS, model_cfg=cfg)
    faults = out["faults"]
    print(f"serve: {time.perf_counter() - t0:.2f} s  decode_steps="
          f"{out['decode_steps']}  generated_tokens="
          f"{out['generated_tokens']}  completed="
          f"{out['completed_requests']}/{SERVE_ARGS['num_requests']}",
          flush=True)
    print(f"faults: {json.dumps(faults)}", flush=True)
    if out["completed_requests"] != SERVE_ARGS["num_requests"]:
        failures.append(f"{out['unfinished_requests']} request(s) did not "
                        "complete")
    for key in ("step_faults", "engine_restarts", "retries", "quarantined",
                "quarantined_requests"):
        if faults[key]:
            failures.append(f"serve hid {faults[key]} {key}")
    if out["decode_steps"] <= 0:
        failures.append("serve ran no decode step")

    # 3. engine logits against the float32 reference forward
    engine = srv.make_engine(model, params, prm_head,
                             max_slots=SERVE_ARGS["max_slots"], seed=SEED,
                             temperature=0.0)
    prompt = tasks.gen_problem(np.random.default_rng(SEED)).prompt_tokens()
    diff, scale = logits_vs_reference(model, params, engine, prompt,
                                      REF_DECODE_STEPS)
    print(f"logits vs float32 reference: max|diff|={diff:.6f} "
          f"max|ref|={scale:.6f} ratio={diff / scale:.6f} "
          f"(tolerance {LOGIT_RTOL})", flush=True)
    if not diff <= LOGIT_RTOL * scale:
        failures.append(f"logits differ from the reference by {diff:.6f}, "
                        f"over {LOGIT_RTOL} x {scale:.6f}")

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}", flush=True)
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
