"""Multi-device correctness via subprocess (8 fake CPU devices — the
main test process must keep seeing exactly 1 device; see
``tests/mesh_harness.py`` for the shared runner + JSON protocol)."""
import pytest

from mesh_harness import run_mesh_script

_SCRIPT = r"""
import jax, jax.numpy as jnp, dataclasses, json
from repro.configs import get_config
from repro.models import lm
from repro.distributed.context import DistContext

base = get_config('moe-gpt3-s').reduced()
base = dataclasses.replace(base, compute_dtype='float32')
key, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
B, S = 4, 32
batch = {'tokens': jax.random.randint(key, (B, S), 0, base.vocab_size),
         'labels': jax.random.randint(k2, (B, S), 0, base.vocab_size)}
cfg = dataclasses.replace(base, moe=dataclasses.replace(
    base.moe, num_partitions=2, memory_reuse_strategy='s4'))
params = lm.init(cfg, key)
loss_ref, _ = lm.loss_fn(params, batch, cfg)
g_ref = jax.grad(lambda p: lm.loss_fn(p, batch, cfg)[0])(params)

from repro.compat import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
dist = DistContext(mesh=mesh, dp_axes=('data',), ep_axis='model',
                   tp_axis='model')
with jax.set_mesh(mesh):
    loss_d = jax.jit(lambda p, b: lm.loss_fn(p, b, cfg, dist=dist)[0]
                     )(params, batch)
    g_d = jax.jit(jax.grad(lambda p, b: lm.loss_fn(p, b, cfg,
                                                   dist=dist)[0])
                  )(params, batch)
diffs = jax.tree_util.tree_map(
    lambda a, b: float(jnp.abs(a - b).max()), g_ref, g_d)
print(json.dumps({
    'n_devices': len(jax.devices()),
    'loss_diff': abs(float(loss_ref) - float(loss_d)),
    'max_grad_diff': max(jax.tree_util.tree_leaves(diffs)),
}))
"""

_DECODE_SCRIPT = r"""
import jax, jax.numpy as jnp, dataclasses, json
from repro.configs import get_config
from repro.models import lm
from repro.distributed.context import DistContext

base = get_config('deepseek-v2-lite-16b').reduced()
cfg = dataclasses.replace(base, compute_dtype='float32')
key = jax.random.PRNGKey(0)
params = lm.init(cfg, key)
B = 4
tok = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
cache0 = lm.init_cache(cfg, B, max_len=32, dtype=jnp.float32)
ref, _ = lm.decode_step(params, cache0, tok, cfg)

from repro.compat import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
dist = DistContext(mesh=mesh, dp_axes=('data',), ep_axis='model',
                   tp_axis='model')
with jax.set_mesh(mesh):
    cache1 = lm.init_cache(cfg, B, max_len=32, dtype=jnp.float32)
    out, _ = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, cfg,
                                                    dist=dist)
                     )(params, cache1, tok)
print(json.dumps({'decode_diff': float(jnp.abs(ref - out).max())}))
"""


@pytest.mark.slow
def test_ep_shard_map_matches_single_device():
    res = run_mesh_script(_SCRIPT)
    assert res["n_devices"] == 8
    assert res["loss_diff"] < 1e-3
    assert res["max_grad_diff"] < 5e-3


@pytest.mark.slow
def test_moe_decode_replicated_path_matches():
    res = run_mesh_script(_DECODE_SCRIPT)
    assert res["decode_diff"] < 1e-3


# expert-parallel training as chip_smoke.py --chips 4 runs it: a
# data=1 x model=4 mesh; train() must create the state already sharded
# (experts split over "model") and keep it so across donated steps, and
# step 0's loss must match the unsharded single-device step
_TRAIN_EP_SCRIPT = r"""
import dataclasses, json
import jax, numpy as np
from repro.configs import get_config
from repro.data import SyntheticTokens
from repro.distributed.context import DistContext
from repro.launch.mesh import make_host_mesh
from repro.runtime import TrainOptions, train

base = get_config('moe-gpt3-s').reduced()
cfg = dataclasses.replace(base, compute_dtype='float32',
                          moe=dataclasses.replace(
                              base.moe, num_experts=8, num_partitions=2,
                              memory_reuse_strategy='s4'))
ds = SyntheticTokens(cfg, batch=4, seq=32, seed=0)
opts = TrainOptions(lr=1e-3, warmup=1, total_steps=2)
mesh = make_host_mesh(1, 4)
dist = DistContext(mesh=mesh, dp_axes=('data',), ep_axis='model',
                   tp_axis='model')
state, hist = train(cfg, steps=2, batch_source=ds, opts=opts, dist=dist,
                    adaptive=False)
_, ref = train(cfg, steps=1, batch_source=ds, opts=opts, adaptive=False)
w = state['params']['periods']['l1']['moe']['experts']['w_up']
m = state['opt']['m']['periods']['l1']['moe']['experts']['w_up']
print(json.dumps({
    'w_spec': [str(a) for a in w.sharding.spec],
    'w_shard_experts': sorted({s.data.shape[1]
                               for s in w.addressable_shards}),
    'm_shard_experts': sorted({s.data.shape[1]
                               for s in m.addressable_shards}),
    'n_devices': len(w.sharding.device_set),
    'loss_diff': abs(hist[0]['loss'] - ref[0]['loss']),
}))
"""


def test_train_places_expert_parallel_state_sharded():
    res = run_mesh_script(_TRAIN_EP_SCRIPT, devices=4)
    assert res["n_devices"] == 4
    assert res["w_spec"][1] == "model"            # [periods, experts, ...]
    assert res["w_shard_experts"] == [2]          # 8 experts / 4 chips
    assert res["m_shard_experts"] == [2]          # Adam moments follow
    assert res["loss_diff"] < 1e-3


def test_sharding_rules_divisibility_fallback():
    import jax
    from repro.distributed.sharding import make_rules
    from repro.compat import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(mesh, "train", fsdp=True)
    # heads=56 does not divide the (trivial 1-sized here) axis product —
    # use a synthetic check through spec_for with a fake big extent
    spec = rules.spec_for((56, 128), ("heads", "head_dim"), "wq")
    assert spec is not None


def test_make_production_mesh_requires_512_devices():
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(Exception):
        make_production_mesh()        # only 1 device in this process
