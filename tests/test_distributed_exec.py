"""Sharded-execution equivalence: run the pjit'd steps on 8 host devices
(subprocess, so the placeholder-device XLA flag cannot leak into other
tests) and compare numerics against the unsharded single-device model.

This is the strongest distribution test available without TPUs: it
validates that the sharding rules + logical constraints + collectives
compute the SAME function, not merely that they compile.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke
from repro.distributed.logical import activation_rules, standard_rules
from repro.distributed.sharding import param_pspecs, sanitize_pspecs, \
    shardings
from repro.launch.mesh import make_auto_mesh
from repro.models import Model, cross_entropy_loss

arch = sys_arch = %(arch)r
cfg = smoke(arch).replace(vocab_size=512)
model = Model(cfg)
params = model.init_params(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
b, s = 4, 32
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
embeds = (jnp.asarray(rng.normal(size=(b, s, cfg.d_model)), jnp.float32)
          if cfg.multimodal else None)

# --- single-device reference
ref_logits, _ = model.forward(params, tokens=None if cfg.multimodal
                              else toks, embeds=embeds)

# --- sharded execution on a (2 data x 4 model) mesh
mesh = make_auto_mesh((2, 4), ("data", "model"))
pspecs = sanitize_pspecs(param_pspecs(params), params, mesh)
sharded_params = jax.device_put(params, shardings(mesh, pspecs))
rules = standard_rules(("data",))

def fwd(p, toks, embeds):
    with activation_rules(mesh, rules):
        logits, aux = model.forward(p, tokens=None if cfg.multimodal
                                    else toks, embeds=embeds)
        return logits

out = jax.jit(fwd)(sharded_params, toks, embeds)
err = float(jnp.max(jnp.abs(out - ref_logits)))
scale = float(jnp.max(jnp.abs(ref_logits)))
print(json.dumps({"err": err, "scale": scale,
                  "devices": len(jax.devices())}))
"""


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-130m",
                                  "dbrx-132b", "hymba-1.5b",
                                  "musicgen-medium"])
def test_sharded_forward_matches_single_device(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT % {"arch": arch}],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    # sharded collectives reorder float math; tolerance scaled to logits
    assert out["err"] <= max(2e-3 * max(out["scale"], 1.0), 2e-3), out
