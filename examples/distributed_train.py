"""Distributed training on emulated devices: the sharded train step
executed on 8 host devices (data=4 x model=2), with LARS trust ratios
computed over sharded leaves.

Run: PYTHONPATH=src python examples/distributed_train.py
(Re-execs itself with XLA_FLAGS to expose 8 CPU devices.)
"""

import os
import subprocess
import sys

if os.environ.get("_REPRO_DIST_EXAMPLE") != "1":
    env = dict(os.environ, _REPRO_DIST_EXAMPLE="1",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "") +
               " --xla_force_host_platform_device_count=8")
    raise SystemExit(subprocess.call([sys.executable] + sys.argv, env=env))

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402

from repro.configs import get_config          # noqa: E402
from repro.core import lars                   # noqa: E402
from repro.data import TokenTaskConfig, token_batches  # noqa: E402
from repro.distributed import (batch_pspecs, state_pspecs,  # noqa: E402
                               tree_named)
from repro.launch.mesh import make_mesh       # noqa: E402
from repro.models import build_model          # noqa: E402
from repro.train import create_train_state, make_train_step  # noqa: E402


def main() -> None:
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen3-14b").reduced()
    model = build_model(cfg)
    opt = lars(0.05, trust_coefficient=0.01)
    # packed=False: per-leaf (tree) opt state, so momentum shards
    # leaf-for-leaf with the FSDP params and the trust-ratio norms run
    # over sharded leaves (XLA inserts the cross-shard reductions).
    state = create_train_state(model, opt, jax.random.key(0), packed=False)

    sspecs = state_pspecs(cfg, jax.eval_shape(lambda: state), mesh)
    bspecs = batch_pspecs(cfg, mesh, batch=8)
    state = jax.device_put(state, tree_named(mesh, sspecs))
    step = jax.jit(make_train_step(model, opt, cfg),
                   in_shardings=(tree_named(mesh, sspecs),
                                 tree_named(mesh, bspecs)),
                   out_shardings=(tree_named(mesh, sspecs), None),
                   donate_argnums=(0,))

    wq = state.params["layers"]["attn"]["wq"]
    print(f"mesh {dict(mesh.shape)}; wq global {wq.shape}, "
          f"per-device shard {wq.addressable_shards[0].data.shape}")

    task = TokenTaskConfig(vocab_size=cfg.vocab_size, branching=2, seed=0)
    with jax.set_mesh(mesh):
        for i, t in enumerate(token_batches(task, batch=8, seq_len=32)):
            batch = {"tokens": jax.device_put(
                jnp.asarray(t[:, :32]), tree_named(mesh, bspecs)["tokens"])}
            state, m = step(state, batch)
            if i % 10 == 0:
                print(f"step {i:3d} loss {float(m['loss']):.4f}")
            if i >= 40:
                break
    print("distributed LARS training on a (4, 2) mesh: OK")


main()
