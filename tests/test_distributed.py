"""Multi-device integration (8 forced host devices, subprocess):

* vocab-parallel CE / embedding == dense references
* sharded flash attention == naive attention (values AND grads)
* a small arch train step lowers, compiles and runs on a (2,4) mesh
* cross-mesh checkpoint restore (elastic restart)
"""
import pytest


def test_vocab_parallel_ce_and_embed_match_dense(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.runtime.sharding import ShardingRules, activation_rules
from repro.runtime.losses import vocab_parallel_cross_entropy, vocab_parallel_embed

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(mesh=mesh, batch_axes=("data",), kind="train")
B, S, D, V = 4, 32, 16, 64
ks = jax.random.split(jax.random.key(0), 3)
x = jax.random.normal(ks[0], (B, S, D))
head = jax.random.normal(ks[1], (V, D)) * 0.1
targets = jax.random.randint(ks[2], (B, S), 0, V)
mask = jnp.ones((B, S), jnp.float32)

def dense(x, head, t, m):
    logits = (x @ head.T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
    return (((lse - picked) * m).sum(), m.sum())

with mesh:
    tot_d, cnt_d = dense(x, head, targets, mask)
    f = jax.jit(lambda *a: vocab_parallel_cross_entropy(*a, rules, chunk=8))
    tot_p, cnt_p = f(x, head, targets, mask)
np.testing.assert_allclose(float(tot_p), float(tot_d), rtol=1e-5)
assert float(cnt_p) == float(cnt_d)

# gradients too
gd = jax.grad(lambda x: dense(x, head, targets, mask)[0])(x)
with mesh:
    gp = jax.jit(jax.grad(lambda x: vocab_parallel_cross_entropy(x, head, targets, mask, rules, chunk=8)[0]))(x)
np.testing.assert_allclose(np.asarray(gp), np.asarray(gd), atol=1e-4)

# embedding
tokens = jax.random.randint(jax.random.key(9), (B, S), 0, V)
with mesh:
    e = jax.jit(lambda t, w: vocab_parallel_embed(t, w, rules))(tokens, head)
np.testing.assert_allclose(np.asarray(e), np.asarray(head[tokens]), atol=1e-6)
print("CE+EMBED OK")
""",
        n_devices=8,
    )


def test_sharded_attention_matches_naive(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.models.attention import naive_attention
from repro.runtime.sharding import ShardingRules, activation_rules
from repro.runtime.sharded_attention import sharded_attention

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
B, S, H, KV, hd = 4, 64, 6, 3, 16
ks = jax.random.split(jax.random.key(0), 3)
q = jax.random.normal(ks[0], (B, S, H, hd))
k = jax.random.normal(ks[1], (B, S, KV, hd))
v = jax.random.normal(ks[2], (B, S, KV, hd))

for kind, impl in (("prefill", "allgather"), ("train", "allgather"), ("train", "flash")):
    rules = ShardingRules(mesh=mesh, batch_axes=("data",), kind=kind)
    with mesh:
        out = jax.jit(lambda q, k, v: sharded_attention(q, k, v, rules, causal=True, block_kv=16, impl=impl))(q, k, v)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-5)
    print(kind, impl, "OK")

# train grads through the sharded path == naive grads
rules = ShardingRules(mesh=mesh, batch_axes=("data",), kind="train")
def loss_sharded(q, k, v):
    with mesh:
        return jnp.sum(jnp.sin(sharded_attention(q, k, v, rules, causal=True, block_kv=16, impl="flash")))
def loss_naive(q, k, v):
    return jnp.sum(jnp.sin(naive_attention(q, k, v, causal=True)))
with mesh:
    g1 = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(q, k, v)
g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
for a, b in zip(g1, g2):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=5e-5)
print("GRADS OK")
""",
        n_devices=8,
    )


def test_small_mesh_train_step_runs(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_arch
from repro.models import build_model
from repro.runtime.steps import build_train_step

cfg = get_arch("qwen3-14b").reduced(d_model=64, d_ff=128, n_layers=2, vocab_size=256,
                                    n_heads=4, n_kv_heads=2, head_dim=16)
model = build_model(cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
shape = ShapeConfig("t", 64, 4, "train")
bundle = build_train_step(model, mesh, shape, donate=False)
params = model.init(jax.random.key(0))
from repro.runtime.optimizer import Optimizer, OptimizerConfig
opt = Optimizer(OptimizerConfig(name=cfg.optimizer, moment_dtype=cfg.moment_dtype))
opt_state = opt.init(params)
batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 64), 0, 256)}
with mesh:
    params = jax.device_put(params, bundle.in_shardings[0])
    opt_state = jax.device_put(opt_state, bundle.in_shardings[1])
    losses = []
    for i in range(3):
        params, opt_state, metrics = bundle.fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
assert losses[-1] < losses[0], losses
assert all(np.isfinite(losses))
print("TRAIN STEP OK", [round(l, 3) for l in losses])
""",
        n_devices=8,
    )


def test_elastic_checkpoint_cross_mesh_restore(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.checkpoint import CheckpointManager

from repro.launch.mesh import make_mesh
mesh8 = make_mesh((8,), ("model",))
mesh2 = make_mesh((2, 4), ("data", "model"))
state = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8), NamedSharding(mesh8, P("model")))}
d = tempfile.mkdtemp()
mgr = CheckpointManager(d)
mgr.save(1, state)
target = NamedSharding(mesh2, P(("data", "model"), None))
restored, _ = mgr.restore(state, shardings={"w": target})
np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(64.0).reshape(8, 8))
assert restored["w"].sharding == target
print("ELASTIC RESTORE OK")
""",
        n_devices=8,
    )


def test_ring_attention_matches_naive(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.models.attention import naive_attention
from repro.runtime.sharding import ShardingRules
from repro.runtime.ring_attention import ring_attention_shmap

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(mesh=mesh, batch_axes=("data",), kind="prefill")
B, S, H, KV, hd = 4, 64, 6, 3, 16
ks = jax.random.split(jax.random.key(3), 3)
q = jax.random.normal(ks[0], (B, S, H, hd))
k = jax.random.normal(ks[1], (B, S, KV, hd))
v = jax.random.normal(ks[2], (B, S, KV, hd))
for causal in (True, False):
    with mesh:
        out = jax.jit(lambda q, k, v: ring_attention_shmap(
            q, k, v, rules, causal=causal, block_kv=16, scale=hd**-0.5))(q, k, v)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-5)
    print("ring causal=", causal, "OK")
# the ring schedule must lower to collective-permutes, not all-gathers
with mesh:
    txt = jax.jit(lambda q, k, v: ring_attention_shmap(
        q, k, v, rules, causal=True, block_kv=16, scale=hd**-0.5)).lower(q, k, v).compile().as_text()
assert "collective-permute" in txt
print("RING OK")
""",
        n_devices=8,
    )
