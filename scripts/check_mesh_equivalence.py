"""Subprocess check: a train step on a (2,2) mesh with CLEAVE shardings
produces the same loss/grads as the unsharded single-device step.
Exit 0 on success.  Invoked by tests/test_system.py (slow)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import get_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import model as M
from repro.optim import adam
from repro.parallel.sharding import make_rules

arch = sys.argv[1] if len(sys.argv) > 1 else "granite-moe-1b-a400m"
cfg = get_config(arch).reduced(n_layers=2, d_model=64, d_head=16,
                               vocab_size=256)
key = jax.random.PRNGKey(0)
params = M.init_params(cfg, key)
opt = adam.init(params)
B, S = 4, 32
tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
batch = {"tokens": tokens, "labels": tokens}
if cfg.enc_dec:
    batch["encoder_feats"] = jax.random.normal(key, (B, 2 * S, cfg.d_model))

# single device
step0 = jax.jit(make_train_step(cfg, q_chunk=16, k_chunk=16, loss_chunk=16))
p0, _, m0 = step0(params, opt, batch)

# 2x2 mesh with CLEAVE rules
mesh = make_mesh((2, 2), ("data", "model"))
rules = make_rules(mesh, mode="train")
with mesh:
    step1 = jax.jit(make_train_step(cfg, rules=rules, q_chunk=16,
                                    k_chunk=16, loss_chunk=16))
    p1, _, m1 = step1(params, opt, batch)

l0, l1 = float(m0["loss"]), float(m1["loss"])
print(f"loss single={l0:.6f} mesh={l1:.6f}")
assert abs(l0 - l1) < 5e-3 * max(abs(l0), 1.0), (l0, l1)
for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=5e-2, atol=5e-3)
print("OK: sharded step matches single-device step")
