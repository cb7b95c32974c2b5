"""TPU-native SPMD training (beyond reference parity): the whole train
step compiles to one XLA program over a dp/sp/tp mesh with ring
attention for long sequences.

  python examples/jax/jax_spmd_train.py --dp 2 --sp 2 --tp 2
"""

import os as _os
import sys as _sys

# allow running straight from a source checkout
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.dirname(_os.path.abspath(__file__)))))


import argparse

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models import TransformerConfig
from horovod_tpu.parallel import MeshSpec, build_mesh, make_lm_train_step

parser = argparse.ArgumentParser()
parser.add_argument("--dp", type=int, default=1)
parser.add_argument("--sp", type=int, default=1)
parser.add_argument("--tp", type=int, default=1)
parser.add_argument("--steps", type=int, default=10)
parser.add_argument("--cpu-devices", type=int, default=0,
                    help="force N virtual CPU devices")


def main():
    args = parser.parse_args()
    if args.cpu_devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)

    mesh = build_mesh(MeshSpec(dp=args.dp, sp=args.sp, tp=args.tp))
    cfg = TransformerConfig(vocab_size=1024, d_model=256, n_layers=4,
                            n_heads=8, d_ff=704, max_seq_len=512)
    init, step, jit_step, tok_shd = make_lm_train_step(
        mesh, cfg, optimizer=optax.adamw(3e-4),
        sequence_parallel=args.sp > 1)

    batch = 4 * args.dp
    tokens = jax.random.randint(jax.random.PRNGKey(0),
                                (batch, cfg.max_seq_len), 0,
                                cfg.vocab_size)
    state = init(jax.random.PRNGKey(1), tokens)
    compiled, state = jit_step(state)
    tokens = jax.device_put(tokens, tok_shd)
    for i in range(args.steps):
        state, loss = compiled(state, tokens)
        print(f"step {i} loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
