"""Multi-device semantics on 8 fake CPU devices (subprocess: device count
locks at first jax init, so each scenario runs in its own interpreter)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(body: str) -> str:
    script = (
        'import os\n'
        'os.environ["XLA_FLAGS"] = '
        '"--xla_force_host_platform_device_count=8"\n'
        + body
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_data_parallel_grads_match_single_device():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import model as model_lib
from repro.training import train_loop
from repro.distributed.sharding import axis_rules, param_shardings

cfg = get_config("tspm-mlho", reduced=True)
mdl = model_lib.build(cfg)
params, pspecs = mdl.init(jax.random.PRNGKey(0))
loss_fn = train_loop.make_loss_fn(mdl)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(4, 64, (8, 16)), jnp.int32)}
batch["labels"] = jnp.roll(batch["tokens"], -1, 1)
batch["loss_mask"] = jnp.ones((8, 16), bool)

ref_loss, ref_grads = jax.value_and_grad(
    lambda p, b: loss_fn(p, b)[0])(params, batch)

mesh = jax.make_mesh((4, 2), ("data", "model"))
with axis_rules(mesh):
    shardings = param_shardings(mesh, pspecs)
    p_sh = jax.device_put(params, shardings)
    b_sh = jax.device_put(batch, NamedSharding(mesh, P("data")))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b)[0]))(p_sh, b_sh)
assert abs(float(loss) - float(ref_loss)) < 1e-4, (loss, ref_loss)
for a, b in zip(jax.tree.leaves(ref_grads), jax.tree.leaves(grads)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
print("DP-OK")
""")


def test_sharded_hash_screen_matches_global():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from functools import partial
from jax import shard_map
from repro.core import mining, sparsity
from repro.data import synthea, dbmart

pats, dates, phx, _ = synthea.generate_cohort(n_patients=64, avg_events=16,
                                              seed=4)
db = dbmart.from_rows(pats, dates, phx)
mined = mining.mine_triangular(db.phenx, db.date, db.nevents)
ref = np.asarray(sparsity.screen_hash(mined.seq, mined.mask, 3,
                                      n_buckets_log2=18))

mesh = jax.make_mesh((8,), ("data",))
spec = P("data")
@partial(shard_map, mesh=mesh, in_specs=(spec, spec),
         out_specs=spec)
def sharded_screen(seq, mask):
    return sparsity.screen_hash(seq, mask, 3, n_buckets_log2=18,
                                axis_names=("data",))

seq_sh = jax.device_put(mined.seq, NamedSharding(mesh, spec))
msk_sh = jax.device_put(mined.mask, NamedSharding(mesh, spec))
got = np.asarray(sharded_screen(seq_sh, msk_sh))
assert (got == ref).all(), "patient-sharded screen != global screen"
print("SCREEN-OK", int(got.sum()))
""")


def test_compressed_psum_convergence():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.distributed.compression import compressed_psum_mean

mesh = jax.make_mesh((8,), ("pod",))

# distributed linear regression with int8-compressed gradient allreduce
rng = np.random.default_rng(0)
X = rng.standard_normal((64, 16)).astype(np.float32)
w_true = rng.standard_normal(16).astype(np.float32)
y = X @ w_true

@partial(shard_map, mesh=mesh,
         in_specs=(P(), P("pod"), P("pod"), P("pod")),
         out_specs=(P(), P("pod")))
def step(w, Xs, ys, err):
    pred = Xs @ w
    g = 2 * Xs.T @ (pred - ys) / ys.size
    g_mean, new_err = compressed_psum_mean(g, "pod", err[0])
    return g_mean, new_err[None]  # error feedback stays shard-local

# jit the shard_map'd step once: the loop below runs it 300 times
step = jax.jit(step)
w = jnp.zeros(16)
err = jax.device_put(jnp.zeros((8, 16)), NamedSharding(mesh, P("pod")))
Xd = jax.device_put(X, NamedSharding(mesh, P("pod")))
yd = jax.device_put(y, NamedSharding(mesh, P("pod")))
for i in range(300):
    g, err = step(w, Xd, yd, err)
    w = w - 0.1 * g
    # one step in flight at a time: queued work of the next step can hold
    # the CPU pool threads that the 8 all-reduce participants need, and
    # on a loaded host the rendezvous then aborts after 40 s
    w.block_until_ready()
final = float(jnp.mean((X @ w - y) ** 2))
assert final < 1e-3, final
print("COMPRESS-OK", final)
""")


def test_elastic_reshard_across_meshes():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import model as model_lib
from repro.training import train_loop, checkpoint, elastic
import tempfile

cfg = get_config("tspm-mlho", reduced=True)
mdl = model_lib.build(cfg)
state, pspecs = train_loop.init_state(mdl, jax.random.PRNGKey(0))
sp = train_loop.state_pspecs(pspecs)

big = jax.make_mesh((4, 2), ("data", "model"))
small = jax.make_mesh((2, 2), ("data", "model"))  # "lost" half the fleet

st_big = elastic.reshard(state, big, sp)
with tempfile.TemporaryDirectory() as d:
    checkpoint.save(d, 0, st_big)
    restored, _ = checkpoint.restore(checkpoint.latest(d), state)
    st_small = elastic.reshard(restored, small, sp)
for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(st_small)):
    assert (np.asarray(a) == np.asarray(b)).all()
devs = {d for x in jax.tree.leaves(st_small)
        for d in x.sharding.device_set}
assert len(devs) == 4, devs
print("ELASTIC-OK")
""")


def test_tp_sharded_forward_matches_replicated():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import model as model_lib
from repro.distributed.sharding import axis_rules, param_shardings

cfg = get_config("gemma2-2b", reduced=True).replace(fsdp=True)
mdl = model_lib.build(cfg)
params, pspecs = mdl.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(1)
batch = {"tokens": jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32)}
ref, _ = mdl.apply(params, batch, mode="train")

mesh = jax.make_mesh((2, 4), ("data", "model"))
with axis_rules(mesh):
    p_sh = jax.device_put(params, param_shardings(mesh, pspecs))
    b_sh = jax.device_put(batch, NamedSharding(mesh, P("data")))
    got, _ = jax.jit(lambda p, b: mdl.apply(p, b, mode="train"))(p_sh, b_sh)
np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=2e-4,
                           rtol=2e-4)
print("TP-OK")
""")


def test_shard_map_ep_matches_dense_moe():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.distributed.sharding import axis_rules, param_shardings
from repro.models import model as model_lib
from repro.launch.mesh import make_test_mesh

cfg = get_config("deepseek-moe-16b", reduced=True).replace(
    capacity_factor=16.0, moe_dispatch="gspmd")
mdl = model_lib.build(cfg)
params, pspecs = mdl.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, 256, (4, 16)), jnp.int32)}
ref, aux_ref = mdl.apply(params, batch, mode="train")

mesh = make_test_mesh((2, 4), ("data", "model"))
mdl2 = model_lib.build(cfg.replace(moe_dispatch="shard_map_ep", fsdp=True))
with axis_rules(mesh):
    p_sh = jax.device_put(params, param_shardings(mesh, pspecs, params))
    b_sh = jax.device_put(batch, NamedSharding(mesh, P("data")))
    got, aux = jax.jit(lambda p, b: mdl2.apply(p, b, mode="train"))(p_sh, b_sh)
np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=3e-4,
                           rtol=3e-4)
assert abs(float(aux_ref) - float(aux)) < 1e-6
print("EP-OK")
""")


def test_slstm_shard_map_grads_match():
    """The shard_map'd sLSTM (per-step dR psum fix) is gradient-exact."""
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.distributed.sharding import axis_rules, param_shardings
from repro.models import model as model_lib
from repro.launch.mesh import make_test_mesh

cfg = get_config("xlstm-125m", reduced=True)
mdl = model_lib.build(cfg)
params, pspecs = mdl.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(1)
batch = {"tokens": jnp.asarray(rng.integers(0, 256, (4, 16)), jnp.int32)}

def loss(p, b):
    logits, _ = mdl.apply(p, b, mode="train")
    return (logits.astype(jnp.float32) ** 2).mean()

ref_l, ref_g = jax.value_and_grad(loss)(params, batch)

mesh = make_test_mesh((4, 2), ("data", "model"))
with axis_rules(mesh):  # activates the shard_map path
    p_sh = jax.device_put(params, param_shardings(mesh, pspecs, params))
    b_sh = jax.device_put(batch, NamedSharding(mesh, P("data")))
    l, g = jax.jit(jax.value_and_grad(loss))(p_sh, b_sh)
assert abs(float(l) - float(ref_l)) < 1e-4 * max(abs(float(ref_l)), 1)
for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(g)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                               rtol=3e-3)
print("SLSTM-SMAP-OK")
""")
