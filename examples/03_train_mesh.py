"""Train the tiny transformer on a sharded mesh (DP+FSDP), single host.

The SAME code runs on a TPU pod: the mesh just gets real chips.
"""
import jax
import jax.numpy as jnp

from ray_tpu.models import configs
from ray_tpu.models.training import default_optimizer, make_train_step
from ray_tpu.parallel import MeshConfig, build_mesh

mesh = build_mesh(MeshConfig(fsdp=-1))          # all local devices
print("mesh:", dict(zip(mesh.axis_names, mesh.devices.shape)))

cfg = configs.TINY
init_fn, step_fn = make_train_step(
    cfg, mesh, optimizer=default_optimizer(3e-4, warmup=5,
                                           total_steps=100))
state = init_fn(jax.random.key(0))
# The batch divides over the data axes, so every device gets a shard.
tokens = jax.random.randint(jax.random.key(1), (2 * mesh.devices.size, 129),
                            0, cfg.vocab_size, dtype=jnp.int32)
for step in range(5):
    state, metrics = step_fn(state, {"tokens": tokens})
    print(f"step {step}: loss={float(metrics['loss']):.3f}")
