"""End-to-end integration: trainer with restart, dry-run on a small mesh."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import SMOKES
from repro.optim import OptHParams
from repro.train import TrainConfig
from repro.train.trainer import Trainer, TrainerConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_trainer_end_to_end_with_restart(tmp_path):
    arch = SMOKES["tinyllama-1.1b"]
    hp = OptHParams(lr_peak=5e-3, warmup_steps=2, total_steps=16)
    tcfg = TrainConfig(microbatches=1, remat="none")

    run1 = TrainerConfig(batch=4, seq=32, steps=8, ckpt_dir=str(tmp_path), ckpt_every=4, log_every=100)
    t1 = Trainer(arch, hp, tcfg, run1)
    s1 = t1.train()
    assert s1["steps"] == 8

    # restart: resumes from step 8, runs 8 more
    run2 = TrainerConfig(batch=4, seq=32, steps=16, ckpt_dir=str(tmp_path), ckpt_every=4, log_every=100)
    t2 = Trainer(arch, hp, tcfg, run2)
    s2 = t2.train()
    assert s2["steps"] == 8  # only the remaining steps
    # Restart semantics, robust to per-step loss noise at this tiny scale:
    # the resumed run starts from the trained checkpoint (well below the
    # from-scratch initial loss, i.e. not re-initialized) …
    init_loss = t1.metrics_log[0]["loss"]
    assert t2.metrics_log[0]["loss"] < init_loss
    # … and continued training stays sane (no divergence after restore).
    assert s2["final_loss"] < init_loss


def test_trainer_straggler_watchdog():
    arch = SMOKES["tinyllama-1.1b"]
    hp = OptHParams(total_steps=6)
    t = Trainer(arch, hp, TrainConfig(), TrainerConfig(batch=2, seq=16, steps=6, log_every=100))
    t.train()
    # first (compile) step is typically flagged relative to later medians —
    # the watchdog mechanism itself must function without error
    assert isinstance(t.straggler_steps, list)


@pytest.mark.slow
def test_dryrun_cell_small_mesh_subprocess(tmp_path):
    """The dry-run machinery end-to-end on a 16-device host mesh."""
    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.dryrun import dryrun_cell
res = dryrun_cell("tinyllama-1.1b", "decode_32k")
assert res["status"] == "ok", res
assert res["n_devices"] == 256
assert sum(res["collective_bytes"].values()) > 0
print("DRYRUN_OK", res["mesh"])
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=600)
    assert "DRYRUN_OK 16x16" in out.stdout, out.stderr[-2000:]


def test_multipod_mesh_shapes_subprocess():
    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
m2 = make_production_mesh(multi_pod=True)
assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
assert m2.devices.shape == (2, 16, 16) and m2.axis_names == ("pod", "data", "model")
print("MESH_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=300)
    assert "MESH_OK" in out.stdout, out.stderr[-2000:]


def test_sharded_train_step_on_test_mesh_subprocess():
    """Real (allocated) sharded train step on an 8-device host mesh —
    verifies the sharding rules run, not just compile."""
    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import SMOKES
from repro.launch.mesh import make_rules, make_test_mesh
from repro.optim import OptHParams
from repro.sharding.logical import use_rules
from repro.sharding.params import batch_specs, param_specs, tree_shardings
from repro.train import TrainConfig, init_train_state, make_train_step

mesh = make_test_mesh((4, 2))
rules = make_rules(mesh)
cfg = SMOKES["tinyllama-1.1b"]
with use_rules(rules), mesh:
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    hp = OptHParams(lr_peak=5e-3, warmup_steps=1, total_steps=8)
    step = jax.jit(make_train_step(cfg, hp))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    l0 = None
    for _ in range(6):
        state, met = step(state, batch)
        l0 = l0 or float(met["loss"])
    assert float(met["loss"]) < l0
print("SHARDED_TRAIN_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=600)
    assert "SHARDED_TRAIN_OK" in out.stdout, out.stderr[-2000:]


def test_sequence_parallel_attention_matches_default_subprocess():
    """SP attention (seq_act→model) must be numerically equivalent to the
    default q-chunked path — same math, different partitioning."""
    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import SMOKES
from repro.launch.mesh import make_rules, make_test_mesh
from repro.models import forward_train, init_params
from repro.sharding.logical import use_rules

mesh = make_test_mesh((2, 4))
for name in ("qwen2-7b", "minicpm3-4b"):
    cfg = SMOKES[name].variant(dtype="float32", n_heads=6, n_kv_heads=2 if name=="qwen2-7b" else 6)
    if name == "minicpm3-4b":
        cfg = cfg.variant(n_kv_heads=6)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    ref, _ = forward_train(params, cfg, {"tokens": toks})  # no mesh: default path
    rules = make_rules(mesh, overrides={"seq_act": "model", "heads": None, "kv_heads": None})
    with use_rules(rules), mesh:
        sp, _ = jax.jit(lambda p, t: forward_train(p, cfg, {"tokens": t}))(params, toks)
    err = float(jnp.max(jnp.abs(ref - sp)))
    assert err < 2e-4, (name, err)
print("SP_EQUIV_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=600)
    assert "SP_EQUIV_OK" in out.stdout, out.stderr[-2000:]


def test_gpipe_pipeline_parallelism_subprocess():
    """GPipe over a 4-stage mesh axis ≡ sequential stage application."""
    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.sharding.pipeline import gpipe

n_stages, M, B, D = 4, 6, 2, 16
mesh = jax.make_mesh((4,), ("pod",))
rng = jax.random.PRNGKey(0)
params = jax.random.normal(rng, (n_stages, D, D)) * 0.3
micro = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))

stage_fn = lambda w, x: jnp.tanh(x @ w)
out = gpipe(stage_fn, params, micro, mesh, axis="pod")

ref = micro
for s in range(n_stages):
    ref = jnp.tanh(ref @ params[s])
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-5, err
print("GPIPE_OK", err)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=300)
    assert "GPIPE_OK" in out.stdout, out.stderr[-2000:]
