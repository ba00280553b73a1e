"""Sharding plans + a real (small-mesh) dry run in a subprocess.

The production 512-device dry-run is exercised by
``python -m repro.launch.dryrun`` (results in results/dryrun/); here we
check the plan trees are coherent and that lower+compile works on an
8-device host mesh from a clean subprocess (device count must be set
before jax initializes).
"""
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import ASSIGNED, get_config
from repro.distributed import shard_plan
from repro.models import model_zoo as zoo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("name", ASSIGNED)
def test_param_pspecs_match_tree(name):
    cfg = get_config(name)
    model = zoo.build(cfg, tp=16)
    specs = zoo.param_specs(model)
    pspecs = shard_plan.param_pspecs(model)
    flat_s, tdef_s = jax.tree_util.tree_flatten(specs)
    flat_p = tdef_s.flatten_up_to(pspecs)
    assert len(flat_s) == len(flat_p)
    for leaf, spec in zip(flat_s, flat_p):
        # spec rank must not exceed tensor rank, and every sharded dim
        # must divide by the mesh axis size it is mapped to
        assert len(spec) <= len(leaf.shape), (spec, leaf.shape)
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            size = {"data": 16, "model": 16, "pod": 2}[ax] \
                if isinstance(ax, str) else 16
            assert dim % size == 0, (name, spec, leaf.shape, ax)


def test_rules_spec():
    r = shard_plan.default_rules(multi_pod=True)
    assert r.spec("batch", "seq") == jax.sharding.PartitionSpec(
        ("pod", "data"), None)
    r2 = shard_plan.default_rules(seq_parallel=True)
    assert r2.spec("batch", "kv_seq") == jax.sharding.PartitionSpec(
        None, ("data",))


def test_shard_noop_without_mesh():
    from repro.distributed.api import shard
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    assert shard(x, "batch", None) is x


SMALL_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.distributed import shard_plan
from repro.distributed.api import use_rules, make_rules
from repro.models import model_zoo as zoo
from repro.training.trainer import TrainConfig, make_train_step

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4, model=2)
cfg = get_smoke_config("qwen3-14b")
model = zoo.build(cfg, tp=2)
rules = make_rules(batch=("data",), heads="model", kv_heads="model",
                   ff="model", vocab="model", experts="model")
params = zoo.init_params(model, jax.random.key(0))
pspecs = shard_plan.param_pspecs(model)
N = lambda t: shard_plan.named(mesh, t)
params = jax.device_put(params, N(pspecs))

step = make_train_step(model, TrainConfig())
from repro.training.optimizer import adamw_init
opt = adamw_init(params)
ef = {"_": jnp.zeros(())}
batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
         "labels": jnp.zeros((8, 32), jnp.int32)}
batch = jax.device_put(batch, N({"tokens": jax.sharding.PartitionSpec(("data",), None),
                                 "labels": jax.sharding.PartitionSpec(("data",), None)}))

def wrapped(p, o, e, b):
    with use_rules(mesh, rules):
        return step(p, o, e, b)

out = jax.jit(wrapped)(params, opt, ef, batch)
loss = float(out[3]["loss"])
assert loss == loss and loss > 0, loss

# compare with single-device result
model1 = zoo.build(cfg, tp=2)
params1 = jax.device_put(jax.tree.map(lambda x: jax.numpy.asarray(x), params))
out1 = jax.jit(step)(params1, adamw_init(params1), {"_": jnp.zeros(())},
                     {k: jax.numpy.asarray(v) for k, v in batch.items()})
import numpy as np
np.testing.assert_allclose(loss, float(out1[3]["loss"]), rtol=5e-3)
print("SMALL-MESH-OK", loss)
"""


@pytest.mark.slow
def test_small_mesh_train_step_subprocess():
    """8 host devices, (4 data x 2 model) mesh: the sharded train step
    compiles, runs, and matches the unsharded loss."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SMALL_MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SMALL-MESH-OK" in out.stdout


def test_dryrun_results_exist_and_clean():
    """The production dry-run artifacts (512 devices, both meshes) must
    exist for every non-skipped cell and contain no failures."""
    import glob
    import json
    d = os.path.join(REPO, "results", "dryrun")
    files = glob.glob(os.path.join(d, "*_baseline.json"))
    if not files:
        pytest.skip("dry-run artifacts not generated yet")
    n_ok = n_skip = 0
    for f in files:
        r = json.load(open(f))
        assert "error" not in r, (f, r.get("error"))
        if "skipped" in r:
            n_skip += 1
        else:
            n_ok += 1
            assert r["cost"].get("flops", 0) > 0
    assert n_ok >= 64, (n_ok, n_skip)
