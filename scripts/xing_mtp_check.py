"""Xing4.0-29B-A4B's multi-token module at the published widths, on the
chip, against the plain reference (``benchmarks/reference/xing.py``):

    chiprun --chips 1 -- python3 scripts/xing_mtp_check.py --seeds 2

The benchmark's cell runs WITHOUT the module (it lies on the pipeline's
last stage, and with it the cell's training state does not fit one
chip: ``benchmarks/configs/xing4.0-29b-a4b.json``).  This is the
comparison the cell's traced run makes, made once with the module ON at
a depth that fits: the one leading dense layer, ONE expert layer and the
module (528,194,466 parameters), two sequences, the loss (program's own
routing) and both gradients (at the reference's routing,
``reference/xing_paired.py``) under the cell's own ``LOSS_RTOL`` and
``GRAD_RTOL``.  One process that holds the chip, no runtime; one JSON
line a seed, exit code 0 only if every comparison held."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import jax
    import numpy as np
    from flax.core import meta

    from benchmarks.reference import xing as ref
    from benchmarks.reference import xing_paired as paired
    from ray_tpu.models import deepseek_v3 as ds

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--seed0", type=int, default=2 ** 31 + 540)
    parser.add_argument("--seq", type=int, default=2048)
    args = parser.parse_args(argv)

    cfg = ds.DeepseekV3Config.xing4_0_29b_a4b_share(
        remat="full", num_layers=1, num_mtp_layers=1, max_seq_len=args.seq)
    model = ds.DeepseekV3(cfg)
    sizes = {"n_layer": cfg.num_layers, "n_head": cfg.num_heads,
             "ln_eps": cfg.rms_eps}
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    n_params = sum(a.size for a in jax.tree.leaves(shapes))
    make = jax.jit(lambda k: ref.init_like(shapes, k))
    loss_of = jax.jit(lambda p, t: ds.loss_fn(model, p, t))
    ref_loss_of = jax.jit(lambda p, t: ref.loss(p, t, **sizes))
    grad_of = jax.jit(jax.grad(lambda p, t: paired.program_loss(
        model, p, t, with_misrouted=True), has_aux=True))
    ref_grad_of = jax.jit(jax.grad(lambda p, t: ref.loss(p, t, **sizes)))
    error = jax.jit(ref.grad_error)
    device = jax.devices()[0]

    ok = True
    for seed in range(args.seed0, args.seed0 + args.seeds):
        params = make(jax.random.PRNGKey(seed % (2 ** 31)))
        tokens = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (2, cfg.max_seq_len), dtype=np.int32)
        loss, want = float(loss_of(params, tokens)), \
            float(ref_loss_of(params, tokens))
        g_ref = ref_grad_of(params, tokens)
        g, misrouted = grad_of(params, tokens)
        line = {"seed": seed, "device": device.device_kind,
                "platform": device.platform, "parameters": int(n_params),
                "sequences": 2, "seq": cfg.max_seq_len, "loss": loss,
                "ref_loss": want, "loss_err": abs(loss - want) / abs(want),
                "grad_err": float(error(g, g_ref)),
                "misrouted_share": float(misrouted),
                "loss_rtol": ref.LOSS_RTOL, "grad_rtol": ref.GRAD_RTOL}
        del g, g_ref, params
        line["ok"] = line["loss_err"] <= ref.LOSS_RTOL and \
            line["grad_err"] <= ref.GRAD_RTOL
        ok = ok and line["ok"]
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
