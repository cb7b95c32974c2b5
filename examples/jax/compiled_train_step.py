"""The compiled Horovod train step: forward, backward, cross-rank
gradient pmean, and the optimizer update as ONE XLA program per rank
step (the reference's in-graph XLA-ops capability,
``horovod/tensorflow/xla_mpi_ops.cc``, done TPU-natively).

  python examples/jax/compiled_train_step.py            # local devices
  python examples/jax/compiled_train_step.py --cpu-devices 4
"""

import os as _os
import sys as _sys

# allow running straight from a source checkout
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.dirname(_os.path.abspath(__file__)))))

import argparse

parser = argparse.ArgumentParser()
parser.add_argument("--steps", type=int, default=20)
parser.add_argument("--batch", type=int, default=32)
parser.add_argument("--cpu-devices", type=int, default=0,
                    help="run on N virtual CPU devices instead of the "
                         "real accelerators")
args = parser.parse_args()

if args.cpu_devices:
    _os.environ["HOROVOD_TPU_PLATFORM"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.cpu_devices)

import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd


def loss_fn(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["w1"])
    pred = h @ params["w2"]
    return jnp.mean((pred - y) ** 2)


def main():
    def per_rank():
        rank, size = hvd.rank(), hvd.size()
        rng = np.random.RandomState(0)
        w = rng.randn(16, 1).astype(np.float32)
        params = {
            "w1": rng.randn(16, 32).astype(np.float32) * 0.1,
            "w2": rng.randn(32, 1).astype(np.float32) * 0.1,
        }

        # every rank sees its own data shard; the step averages the
        # gradients INSIDE the compiled program (lax.pmean over the
        # process set's mesh axis)
        data_rng = np.random.RandomState(100 + rank)
        step = hvd.make_compiled_train_step(loss_fn,
                                            optax.adamw(1e-2))
        state = step.init_state(params)
        for i in range(args.steps):
            x = data_rng.randn(args.batch, 16).astype(np.float32)
            y = (x @ w).astype(np.float32)
            state, loss = step(state, (x, y))
            if rank == 0 and i % 5 == 0:
                print(f"step {i:3d} loss {float(loss):.5f}")
        return float(loss)

    losses = hvd.run(per_rank)
    print(f"final losses per rank (identical replicas): {losses}")


if __name__ == "__main__":
    main()
