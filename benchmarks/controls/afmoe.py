"""The CONTROLS of the AFMoE cell's two comparisons, at the
configuration's own size, in one process that holds the chip (no
runtime, no gang: the builder runs it, the benchmark's runs never do):

    python3 benchmarks/controls/afmoe.py --config trinity-mini --seeds 2

For each seed, as ``benchmarks/kinds/train.py`` does it: the weights from
the seed, the first batch, the program's loss against the reference's
(``LOSS_RTOL``), and the gradients of program and reference at depth 2 on
two sequences (``GRAD_RTOL``; the program's at the reference's routing,
``reference/afmoe_paired.py``, as ``entry.loss_fn`` has it).  Beside the
sound program:

* how many tokens' top-k choice differs between the program's bf16 and
  the reference's f32 router, per expert layer, by how much the
  reference's scores prefer its own set where they differ (a near tie
  reads small), and the reference's loss GIVEN the program's choices
  beside the loss with its own;
* the gradient error WITHOUT the pairing (``grad_err_own_routing``), which
  is what the flips add, and the share of tokens the program's own
  routers misroute (``reference/afmoe_paired.py``; past its limit the
  paired gradient is zero and its error reads exactly 1);
* the controls, each of which has to fail at least one of the two
  limits, or the comparison that decides ``correct`` decides nothing:
  ``lower_precision`` (every float32 the configuration states lowered
  to bfloat16, the nearest precision below: the parameters, the router's
  scores and the head's logits; the parameters alone are ``bf16_params``,
  which the step-0 checks cannot see, because the program's matmuls
  round the weights to bfloat16 anyway), ``window_short`` (the
  window one tile of 1024 short), ``no_route_scale`` (``route_scale``
  left out), ``drops`` (the layer's row plan made with buffers for half
  the rows an even router lands, so rows are dropped: built here, the
  program has no such mode).

One JSON line a seed; exit code 0 only if every sound comparison held
and every control failed one.  ``rehearse`` (tests): tiny sizes, CPU."""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, rehearse=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta
    from unittest import mock

    from benchmarks.kinds.train import resolve

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="trinity-mini")
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--seed0", type=int, default=2 ** 31 + 11)
    parser.add_argument("--skip-grads", action="store_true")
    parser.add_argument("--skip-loss", action="store_true")
    parser.add_argument("--loss-seeds", type=int, default=None,
                        help="the loss comparison on the first N seeds "
                        "alone (default: all)")
    parser.add_argument("--only", default="", metavar="A,B",
                        help="run these controls alone (default: all)")
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        conf = json.load(f)
    entry, assumed = conf["entry"], conf["assumed"]
    ref = importlib.import_module(conf["reference"])
    paired_loss = resolve(entry["loss_fn"])
    Model = resolve(entry["model"])
    program = importlib.import_module(Model.__module__)
    base = dataclasses.replace(
        resolve(entry["config"])(**entry["config_args"]),
        **(rehearse or {}).get("config_args", {}))
    batch = (rehearse or {}).get("batch", assumed["batch"])
    sizes = {"n_layer": base.num_layers, "n_head": base.num_heads,
             "ln_eps": assumed["program_layer_norm_epsilon"]}
    ref_kw = (rehearse or {}).get("ref_kw", {})

    real_plan = program.gm.plan_rows

    def short_plan(idx, first, held, *, block_m):
        # buffers for half of what an even router lands here
        return real_plan(idx, first, held, block_m=block_m,
                         row_bound=idx.size * held // (2 * base.num_experts))

    # name -> (configuration, rounded parameters?, loss_fn keywords,
    #          patch of the program while it is traced)
    lower = dataclasses.replace(base, router_dtype=jnp.bfloat16)
    variants = {
        "window_short": (dataclasses.replace(
            base, window=base.window - min(1024, base.window // 2)),
            False, {}, None),
        "no_route_scale": (dataclasses.replace(base, route_scale=1.0),
                           False, {}, None),
        "drops": (base, False, {}, short_plan),
        "bf16_params": (base, True, {}, None),
        "lower_precision": (lower, True,
                            {"head_logits_dtype": jnp.bfloat16}, None),
    }
    if args.only:
        variants = {k: v for k, v in variants.items()
                    if k in args.only.split(",")}

    def traced(fn, patch):
        """``fn`` jitted; a variant that breaks the program does so
        while it is traced."""
        jitted = jax.jit(fn)
        if patch is None:
            return jitted

        def call(*a):
            with mock.patch.object(program.gm, "plan_rows", patch):
                return jitted(*a)
        return call

    def to_bf16(tree):
        # an astype round trip inside one jit is dropped on the chip
        # (PERF.md, PR 27): reduce_precision computes in bfloat16 there
        return jax.jit(lambda t: jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, 8, 7), t))(tree)

    def tree_for(cfg, key):
        one = Model(dataclasses.replace(cfg, **{entry["depth_arg"]: 1}))
        shapes = meta.unbox(ref.expand_layers(jax.eval_shape(
            lambda: one.init_params(key, batch=1)), cfg.num_layers))
        return jax.jit(lambda k: ref.init_like(shapes, k))(key)

    # ---- the loss: full depth, a sequence at a time as the harness
    own_sum = jax.jit(lambda p, t: ref.loss_sum(p, t, **sizes, **ref_kw))
    given_sum = jax.jit(lambda p, t, c: ref.loss_sum(
        p, t, choices=c, **sizes, **ref_kw))
    gaps_of = jax.jit(lambda p, t, c: [
        (d.sum(), g.max()) for d, g in ref.flip_gaps(
            p, t, c, **sizes, **ref_kw)])
    loss_of = {name: traced(lambda p, t, cfg=cfg, kw=kw: program.loss_fn(
        Model(cfg), p, t, **kw), patch)
        for name, (cfg, _, kw, patch) in
        {"sound": (base, False, {}, None), **variants}.items()}

    # ---- the gradients, as the harness's gradient_check: depth 2, two
    # sequences, weights from PRNGKey(1)
    depth = min(2, base.num_layers)
    gsizes = dict(sizes, n_layer=depth)

    def shallow(cfg):
        return Model(dataclasses.replace(cfg, **{entry["depth_arg"]: depth}))

    g_ref_of = jax.jit(jax.grad(lambda q, t: ref.loss(
        q, t, **gsizes, **ref_kw)))
    grad_of = {name: traced(jax.grad(
        lambda q, t, cfg=cfg, kw=kw: paired_loss(
            shallow(cfg), q, t, arch=ref_kw.get("arch"),
            with_misrouted=True, **kw), has_aux=True),
        patch) for name, (cfg, _, kw, patch) in
        {"sound": (base, False, {}, None), **variants}.items()}
    grad_own = jax.jit(jax.grad(lambda q, t: program.loss_fn(
        shallow(base), q, t)))
    error = jax.jit(ref.grad_error)

    ok = True
    for seed in range(args.seed0, args.seed0 + args.seeds):
        line = {"seed": seed, "loss_rtol": ref.LOSS_RTOL,
                "grad_rtol": ref.GRAD_RTOL}
        line.update({name: {} for name in variants})
        with_loss = not args.skip_loss and (
            args.loss_seeds is None or seed < args.seed0 + args.loss_seeds)
        if with_loss:
            key = jax.random.PRNGKey(seed % (2 ** 31))
            params = tree_for(base, key)
            rounded = to_bf16(params)
            tokens = np.random.default_rng(seed).integers(
                0, base.vocab_size, (batch, base.max_seq_len),
                dtype=np.int32)
            choices = program.router_choices(Model(base), params, tokens)
            per_seq = base.max_seq_len
            own = given = 0.0
            flips = [0] * base.num_layers
            gap = [0.0] * base.num_layers
            for i in range(batch):
                row = tokens[i:i + 1]
                mine = [c[i * per_seq:(i + 1) * per_seq] for c in choices]
                own += float(own_sum(params, row))
                given += float(given_sum(params, row, mine))
                for n, (d, g) in enumerate(gaps_of(params, row, mine)):
                    flips[n] += int(d)
                    gap[n] = max(gap[n], float(g))
            count = batch * (per_seq - 1)
            own, given = own / count, given / count

            def rel(x):
                return abs(x - own) / abs(own)

            loss = float(loss_of["sound"](params, tokens))
            line.update({
                "ref_loss": own, "ref_loss_given_program_choices": given,
                "loss": loss, "loss_err": rel(loss),
                "loss_err_given_program_choices": abs(loss - given) / given,
                "topk_flips_per_layer": flips,
                "flip_score_gap_max_per_layer": gap,
                "tokens": batch * per_seq})
            for name, (_, low, _, _) in variants.items():
                line[name]["loss_err"] = rel(float(loss_of[name](
                    rounded if low else params, tokens)))
            print(f"[controls] losses: {json.dumps(line)}", file=sys.stderr,
                  flush=True)
            del params, rounded, choices, mine

        if not args.skip_grads:
            small = shallow(base).config
            gtok = np.random.default_rng(seed + 1).integers(
                0, small.vocab_size, (2, small.max_seq_len), dtype=np.int32)
            gshapes = meta.unbox(jax.eval_shape(lambda: shallow(
                base).init_params(jax.random.PRNGKey(1), batch=2)))
            gparams = jax.jit(lambda k: ref.init_like(gshapes, k))(
                jax.random.PRNGKey(1))
            grounded = to_bf16(gparams)
            g_ref = g_ref_of(gparams, gtok)
            g, share = grad_of["sound"](gparams, gtok)
            line["grad_err"] = float(error(g, g_ref))
            line["misrouted_share"] = float(share)
            line["grad_err_own_routing"] = float(error(
                grad_own(gparams, gtok), g_ref))
            print(f"[controls] sound: {line['grad_err']} (own routing "
                  f"{line['grad_err_own_routing']}), misrouted "
                  f"{line['misrouted_share']}", file=sys.stderr, flush=True)
            del g
            for name, (_, low, _, _) in variants.items():
                g, share = grad_of[name](grounded if low else gparams, gtok)
                line[name]["grad_err"] = float(error(g, g_ref))
                line[name]["misrouted_share"] = float(share)
                del g
                print(f"[controls] {name}: {line[name]}", file=sys.stderr,
                      flush=True)
            del g_ref, gparams, grounded

        sound = line.get("loss_err", 0.0) <= ref.LOSS_RTOL and \
            line.get("grad_err", 0.0) <= ref.GRAD_RTOL
        caught = {name: line[name].get("loss_err", 0.0) > ref.LOSS_RTOL
                  or line[name].get("grad_err", 0.0) > ref.GRAD_RTOL
                  for name in variants if name != "bf16_params"}
        line["sound"], line["caught"] = sound, caught
        ok = ok and sound and all(caught.values())
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
