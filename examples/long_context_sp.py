"""Sequence-parallel long-context training: ring or Ulysses attention.

Trains a small GPT-2 on sequences sharded over the ``sp`` mesh axis —
the configuration where one device cannot hold the full sequence's
attention working set.  On a real TPU slice both schemes run their
per-chunk / local attention on the pallas flash kernels (O(block)
memory, bf16 MXU operands; ring skips fully-future chunks outright).

Usage (8 virtual CPU devices; on a TPU pod just run it):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/long_context_sp.py [ring|ulysses]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.models import GPT2, GPT2Config
from ray_tpu.models.gpt2 import loss_fn
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.mesh import use_mesh
from jax.sharding import NamedSharding, PartitionSpec as P


def main(impl: str = "ring") -> None:
    if impl not in ("ring", "ulysses"):
        raise SystemExit(f"usage: long_context_sp.py [ring|ulysses] "
                         f"(got {impl!r})")
    n = len(jax.devices())
    sp = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    if sp == 1:
        raise SystemExit(
            "need >1 device for sequence parallelism — run with\n"
            "  XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "JAX_PLATFORMS=cpu python examples/long_context_sp.py")
    mesh = build_mesh(MeshConfig(sp=sp, dp=n // sp))

    seq = 512  # tiny for the demo; the sp axis is what matters
    cfg = GPT2Config.tiny(dtype=jnp.float32, attn_impl=impl,
                          max_seq_len=seq,
                          num_heads=4)  # sp must divide num_heads (ulysses)
    model = GPT2(cfg)

    with use_mesh(mesh):  # binds the sp axis for in-model attention
        params = model.init_params(jax.random.PRNGKey(0), batch=1, seq=seq)
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1),
                               (2 * (n // sp), seq), 0, cfg.vocab_size),
            NamedSharding(mesh, P("dp", "sp")))  # sequence SHARDED

        tx = optax.adam(1e-2)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(model, p, tokens))(params)
            updates, opt_state = tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        for i in range(10):
            params, opt_state, loss = step(params, opt_state, tokens)
            if i % 3 == 0:
                print(f"step {i}: loss {float(loss):.4f}  "
                      f"(attn_impl={impl}, sp={sp})")
    final = float(loss)
    print(f"done: loss {final:.4f}")
    assert np.isfinite(final)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "ring")
