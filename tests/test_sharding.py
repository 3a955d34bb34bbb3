"""Sharding resolver + ZeRO spec rules + sharded-vs-unsharded equivalence."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# children stay on the CPU: a TPU belongs to one process, and without a
# platform jax spends minutes probing for accelerators
SUB_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", ROOT), "JAX_PLATFORMS": "cpu"}

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.models import model_api as MA
from repro.optim.adamw import zero1_spec
from repro.sharding.api import DEFAULT_RULES, ShardCtx


class FakeMesh:
    """Shape-only mesh stand-in so resolver tests don't need 256 devices."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def ctx16():
    c = ShardCtx.__new__(ShardCtx)
    c.mesh = FakeMesh({"data": 16, "model": 16})
    c.rules = dict(DEFAULT_RULES)
    return c


def test_divisible_dims_get_model_axis():
    c = ctx16()
    assert c.spec(("vocab", None), (152064, 3584)) == P("model")
    assert c.spec((None, None, "ffn"), (28, 3584, 18944)) == \
        P(None, None, "model")


def test_non_divisible_heads_fall_back_to_replicated():
    c = ctx16()
    # 28 heads % 16 != 0 -> None
    # (singleton-tuple spelling P(("data",)) only compares equal to this on
    # newer jax; the bare form means the same sharding on every version)
    assert c.spec(("batch", None, "heads", None), (256, 4096, 28, 128)) == \
        P("data")
    # 32 heads divides -> sharded
    sp = c.spec(("batch", None, "heads", None), (256, 4096, 32, 128))
    assert sp == P("data", None, "model")


def test_axis_used_once_per_spec():
    c = ctx16()
    # expert takes model; ffn cannot reuse it
    sp = c.spec((None, "expert", None, "ffn"), (28, 64, 2048, 2816))
    assert sp == P(None, "model")


def test_cache_seq_joint_sharding_for_batch1():
    c = ctx16()
    # batch=1 unshardable; cache_seq grabs data+model jointly (256-way)
    sp = c.spec((None, "batch", "cache_seq"), (48, 1, 524288))
    assert sp == P(None, None, ("data", "model"))
    # batch=128 takes data; cache_seq falls back to model only
    sp = c.spec((None, "batch", "cache_seq"), (48, 128, 32768))
    assert sp == P(None, ("data",), ("model",)) or \
        sp == P(None, "data", "model")


def test_multipod_batch_takes_pod_and_data():
    c = ShardCtx.__new__(ShardCtx)
    c.mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    c.rules = dict(DEFAULT_RULES)
    assert c.spec(("batch", None), (256, 4096)) == P(("pod", "data"))


def test_zero1_spec_insertion():
    mesh = FakeMesh({"data": 16, "model": 16})
    # param sharded on last dim by model; zero1 adds data on a free dim
    sp = zero1_spec(P(None, None, "model"), (28, 3584, 18944), mesh)
    assert sp == P(None, "data", "model")
    # data already used -> unchanged
    sp2 = zero1_spec(P("data", "model"), (256, 4096), mesh)
    assert sp2 == P("data", "model")
    # nothing divisible -> unchanged
    sp3 = zero1_spec(P(), (7, 9), mesh)
    assert sp3 == P()


@pytest.mark.slow
def test_sharded_train_step_matches_unsharded():
    """4-device subprocess: one train step on mesh (2,2) must match the
    single-device result (same loss)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, ShapeConfig
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_cell
from repro.models import model_api as MA
from repro.optim import adamw

cfg = get_config("qwen2-7b").reduced()
shape = ShapeConfig("t", "train", 32, 4)
mod = MA.get_module(cfg)
params = mod.init(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params)
k = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(k, (4, 32), 0, cfg.vocab),
         "labels": jax.random.randint(k, (4, 32), 0, cfg.vocab),
         "mask": jnp.ones((4, 32), jnp.float32)}

cell0 = make_train_cell(cfg, shape, None, microbatches=1)
p0, o0, m0 = cell0.fn(params, opt, batch)

mesh = make_mesh((2, 2), ("data", "model"))
cell = make_train_cell(cfg, shape, mesh, microbatches=1)
ps = jax.tree.map(jax.device_put, params, cell.in_shardings[0])
os_ = jax.tree.map(jax.device_put, opt, cell.in_shardings[1])
bs = {kk: jax.device_put(v, s) for (kk, v), s in
      zip(batch.items(), [cell.in_shardings[2][kk] for kk in batch])}
p1, o1, m1 = cell.jit()(ps, os_, bs)
d = abs(float(m0["loss"]) - float(m1["loss"]))
print("LOSS_DELTA", d)
assert d < 1e-3, d
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(SUB_ENV), cwd=ROOT, timeout=420)
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_dryrun_one_cell_compiles_on_512_devices(tmp_path):
    """The dry-run entrypoint itself (512 fake devices, production mesh)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "hymba-1.5b",
         "--shape", "long_500k", "--mesh", "pod", "--out", str(tmp_path)],
        capture_output=True, text=True,
        env=dict(SUB_ENV), cwd=ROOT, timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok" in r.stdout
