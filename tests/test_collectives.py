"""Proxy collective schedules on an 8-device fake mesh (subprocess: the
device count must be pinned before jax initialises, and the main test
process must keep seeing 1 device)."""
import numpy as np
import pytest

from _subproc import run_devices


def test_proxy_psum_equals_flat():
    out = run_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C
mesh = jax.make_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(0)
for shape in [(8, 16, 4), (8, 5, 3), (8, 64)]:
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    r = C.hierarchical_psum(x, mesh, "data", "pod")
    assert np.allclose(r, jnp.sum(x, 0), rtol=1e-5, atol=1e-5), shape
print("OK")
""")
    assert "OK" in out


def test_two_hop_equals_one_hop_and_manual():
    out = run_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C
mesh = jax.make_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(0)
buf = jnp.asarray(rng.standard_normal((8, 2, 4, 3, 5)), jnp.float32)
def run(fn):
    f = jax.shard_map(lambda b: fn(b[0], "data", "pod")[None],
                      mesh=mesh, in_specs=(P(("pod","data")),),
                      out_specs=P(("pod","data")), check_vma=False)
    return np.asarray(jax.jit(f)(buf))
a = run(C.two_hop_all_to_all)
b = run(C.one_hop_all_to_all)
assert np.allclose(a, b)
bufr = np.asarray(buf).reshape(2,4,2,4,3,5)
expect = np.transpose(bufr, (2,3,0,1,4,5)).reshape(8,2,4,3,5)
assert np.allclose(a, expect)
print("OK")
""")
    assert "OK" in out


def test_proxy_embedding_grad():
    out = run_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C
mesh = jax.make_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(0)
V, D = 32, 4
ids = jnp.asarray(rng.integers(0, V, (8, 6)), jnp.int32)
gv = jnp.asarray(rng.standard_normal((8, 6, D)), jnp.float32)
def f(i, g):
    return C.proxy_embedding_grad(i[0], g[0], V, "data", "pod")
out = jax.jit(jax.shard_map(f, mesh=mesh,
    in_specs=(P(("pod","data")), P(("pod","data"))),
    out_specs=P("data", None), check_vma=False))(ids, gv)
dense = np.zeros((V, D), np.float32)
np.add.at(dense, np.asarray(ids).reshape(-1), np.asarray(gv).reshape(-1, D))
assert np.allclose(np.asarray(out), dense, rtol=1e-5, atol=1e-5)
print("OK")
""")
    assert "OK" in out


def test_sharded_train_step_runs():
    """A reduced arch trains on a 2x2 mesh with the rule-based shardings
    (integration: shardings.py x train_step x GSPMD)."""
    out = run_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import registry
from repro.training.optimizer import adamw
from repro.training.train_step import TrainState, make_train_step
from repro.launch.shardings import (batch_spec, opt_spec, param_spec,
                                    tree_shardings)
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg, fam = registry.get("deepseek-7b", smoke=True)
opt = adamw(lr=1e-3)
params = fam["init"](cfg, jax.random.PRNGKey(0))
state = TrainState.create(params, opt)
sshard = TrainState(
    params=tree_shardings(params, param_spec, mesh, fsdp=True),
    opt_state=tree_shardings(state.opt_state, opt_spec, mesh, fsdp=True),
    step=NamedSharding(mesh, P()))
rng = np.random.default_rng(0)
batch = dict(tokens=jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32),
             labels=jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32))
bshard = tree_shardings(batch, batch_spec, mesh)
step = jax.jit(make_train_step(cfg, fam, opt),
               in_shardings=(sshard, bshard), out_shardings=(sshard, None))
with mesh:
    state2, m = step(state, batch)
    state3, m2 = step(state2, batch)
assert np.isfinite(float(m["loss"])) and np.isfinite(float(m2["loss"]))
# params actually moved by step 2 (step 1 has lr=0 from warmup; the
# loss itself may round equal in bf16)
d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(state2.params),
                        jax.tree.leaves(state3.params)))
assert d > 0, d
assert int(state3.step) == 2
print("OK", float(m["loss"]), float(m2["loss"]), d)
""", n=4, timeout=500)
    assert "OK" in out


def test_sharded_equals_single_device():
    """The sharded train step computes the same loss as unsharded."""
    out = run_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import registry
from repro.training.optimizer import adamw
from repro.training.train_step import TrainState, make_train_step
from repro.launch.shardings import batch_spec, opt_spec, param_spec, tree_shardings
from jax.sharding import NamedSharding, PartitionSpec as P
cfg, fam = registry.get("granite-moe-1b-a400m", smoke=True)
opt = adamw(lr=1e-3)
params = fam["init"](cfg, jax.random.PRNGKey(0))
state = TrainState.create(params, opt)
rng = np.random.default_rng(0)
batch = dict(tokens=jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32),
             labels=jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32))
# single device
_, m0 = jax.jit(make_train_step(cfg, fam, opt))(state, batch)
# 4-device mesh
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sshard = TrainState(
    params=tree_shardings(params, param_spec, mesh, fsdp=False),
    opt_state=tree_shardings(state.opt_state, opt_spec, mesh, fsdp=False),
    step=NamedSharding(mesh, P()))
bshard = tree_shardings(batch, batch_spec, mesh)
step = jax.jit(make_train_step(cfg, fam, opt),
               in_shardings=(sshard, bshard), out_shardings=(sshard, None))
with mesh:
    _, m1 = step(state, batch)
d = abs(float(m0["loss"]) - float(m1["loss"]))
assert d < 1e-2, d
print("OK", d)
""", n=4, timeout=500)
    assert "OK" in out
