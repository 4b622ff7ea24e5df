"""Train GPT-2 with JaxTrainer: gang actors + mesh data parallelism.

Usage: python examples/train_gpt2.py [--steps 30] [--model tiny|small]
                                     [--tpus-per-worker 1]

With ``--tpus-per-worker N`` each gang worker leases N chips from the
raylet and opens exactly those (the path ``chip_smoke.py`` proves); this
process must then not touch a jax backend itself — one process per chip.
On the chip ``--model small`` trains at the published sequence length
(1024); on the CPU it is cut to 128 so the example stays quick.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse

import numpy as np

import ray_tpu
from ray_tpu.train import Checkpoint, JaxTrainer, ScalingConfig, session


def train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax
    from flax.core import meta

    from ray_tpu.core import device_telemetry
    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import make_train_step

    cfg = (GPT2Config.tiny(dtype=jnp.float32)
           if config["model"] == "tiny" else GPT2Config.gpt2_small())
    model = GPT2(cfg)
    rng = jax.random.PRNGKey(session.get_world_rank())
    on_chip = jax.default_backend() == "tpu"
    seq = cfg.max_seq_len if on_chip else min(cfg.max_seq_len, 128)
    params = meta.unbox(model.init_params(rng, batch=1, seq=seq))
    tx = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = tx.init(params)

    # device-plane wiring: compile telemetry on the jitted step, MFU /
    # phase attribution via the session's step monitor (rides the
    # result rows back to the driver as the "device" sibling key)
    step = device_telemetry.instrument_step(
        make_train_step(model, tx), name="train_gpt2.step")
    mon = session.step_monitor()
    mon.flops_per_token = cfg.flops_per_token()

    for i in range(config["steps"]):
        tokens = jax.random.randint(
            jax.random.PRNGKey(i), (config["batch"], seq), 0,
            cfg.vocab_size)
        span = mon.step()
        params, opt_state, loss = step(params, opt_state, tokens)
        span.dispatched()
        span.device_done(loss)
        span.done(tokens=float(tokens.size))
        if i % 10 == 0 or i == config["steps"] - 1:
            ckpt = Checkpoint.from_pytree(params) \
                if session.get_world_rank() == 0 else None
            session.report({"step": i, "loss": float(loss)},
                           checkpoint=ckpt)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--model", default="tiny",
                        choices=("tiny", "small"))
    parser.add_argument("--num-workers", type=int, default=1)
    parser.add_argument("--tpus-per-worker", type=int, default=0)
    args = parser.parse_args()

    ray_tpu.init(ignore_reinit_error=True)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"steps": args.steps, "batch": args.batch,
                           "model": args.model},
        scaling_config=ScalingConfig(num_workers=args.num_workers,
                                     cpus_per_worker=1,
                                     tpus_per_worker=args.tpus_per_worker))
    result = trainer.fit()
    assert result.error is None, result.error
    print(f"final loss: {result.metrics['loss']:.4f} "
          f"(steps={result.metrics['step'] + 1}, "
          f"checkpoint={'yes' if result.checkpoint else 'no'})")


if __name__ == "__main__":
    main()
