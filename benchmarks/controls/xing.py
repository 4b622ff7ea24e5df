"""The CONTROLS of the Xing4.0-29B-A4B cell's two comparisons, at the
configuration's own size, in one process that holds the chip (no
runtime, no gang: the builder runs it, the benchmark's runs never do):

    python3 benchmarks/controls/xing.py --seeds 2

For each seed, as ``benchmarks/kinds/train.py`` does it: the weights from
the seed, the first batch, the program's loss against the reference's
(``LOSS_RTOL``), and the gradients of program and reference at depth 2 on
two sequences (``GRAD_RTOL``; the program's at the reference's routing,
``reference/xing_paired.py``, as ``entry.loss_fn`` has it).  Beside the
sound program: how many tokens' top-4 choice differs between the
program's bf16 stream and the reference's f32 one, per expert layer; the
gradient error WITHOUT the pairing; the share of choices that land on
the held experts; what the hyper-connections made of the batch
(``hc_stats``); and the controls, each of which has to fail at least one
of the two limits, or the comparison that decides ``correct`` decides
nothing:

* ``lower_precision``   every float32 the configuration states lowered
  to bfloat16 (parameters, router, hyper-connection coefficients, head
  logits): the nearest precision below;
* ``coef_bf16``         the hyper-connections' coefficients alone;
* ``sinkhorn_4``        4 Sinkhorn steps for 20;
* ``row_then_column``   a step divides rows first, then columns;
* ``post_gain_1``       ``H_post`` without its 2;
* ``no_lane_norm``      the projection on unnormalised lanes;
* ``first_lane_only``   the head reads lane 0, not the lanes' sum;
* ``scale_192``         the softmax scale without ``m^2``;
* ``plain_rope``        plain frequencies, no YaRN;
* ``no_query_norm``     the query latent's RMS norm left out;
* ``no_route_scale``    ``routed_scaling_factor`` left out.

A control breaks the PROGRAM while it is traced (a patched name or constant of
``ray_tpu.models.deepseek_v3`` or ``ray_tpu.models.hyper``, another
configuration value, rounded parameters): the program has no such
modes.  One JSON line a seed; exit code 0 only if every sound comparison
held and every control failed one (on the chip three pass,
``row_then_column``, ``sinkhorn_4`` and ``coef_bf16``, and the exit code
is 1: what that means stands beside ``GRAD_RTOL`` in
``reference/xing.py``).  ``--weight-keys N``: the gradients over ``N``
draws of the check's weights (the harness's own is the first), and
beside each error the same norm over the connections' own leaves.
``rehearse`` (tests): tiny sizes, CPU."""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def breakages(program, base):
    """name -> (configuration, rounded parameters?, loss_fn keywords,
    [(module, attribute of it, what stands in for it)])."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import hyper

    real_flash = program.flash_attention

    def flash_scale_192(q, k, v, **kw):
        return real_flash(q, k, v, **dict(kw, scale=q.shape[-1] ** -0.5))

    def plain_inv_freq(dim, theta, *_):
        j = jnp.arange(dim // 2, dtype=jnp.float32)
        return theta ** (-2.0 * j / dim), 0, 0

    def rows_first(logits, iters, eps):
        def one(m, _):
            m = m / (m.sum(1, keepdims=True) + eps)
            return m / (m.sum(0, keepdims=True) + eps), None
        return jax.lax.scan(one, jnp.exp(logits), None, length=iters)[0]

    def no_scale(x, dtype):
        return jnp.ones(x.shape[:-1], dtype)

    def first_lane(x, n):
        return x[..., :x.shape[-1] // n]

    class NoNorm(nn.Module):   # the parameter stays, the norm goes
        eps: float

        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.with_partitioning(
                nn.initializers.ones, (None,)), (x.shape[-1],), jnp.float32)
            return x

    replace = dataclasses.replace
    return {
        "lower_precision": (
            replace(base, router_dtype=jnp.bfloat16), True,
            {"head_logits_dtype": jnp.bfloat16},
            [(hyper, "COEF_DTYPE", jnp.bfloat16)]),
        "coef_bf16": (base, False, {}, [(hyper, "COEF_DTYPE", jnp.bfloat16)]),
        "sinkhorn_4": (base, False, {}, [(hyper, "SINKHORN_ITERS", 4)]),
        "row_then_column": (base, False, {},
                            [(hyper, "sinkhorn", rows_first)]),
        "post_gain_1": (base, False, {}, [(hyper, "POST_GAIN", 1.0)]),
        "no_lane_norm": (base, False, {}, [(hyper, "lane_scale", no_scale)]),
        "first_lane_only": (base, False, {},
                            [(hyper, "collapse", first_lane)]),
        "scale_192": (base, False, {},
                      [(program, "flash_attention", flash_scale_192)]),
        "plain_rope": (base, False, {},
                       [(program, "yarn_inv_freq", plain_inv_freq)]),
        "no_query_norm": (base, False, {},
                          [(program, "_QueryNorm", NoNorm)]),
        "no_route_scale": (replace(base, route_scale=1.0), False, {}, []),
    }


def main(argv=None, rehearse=None) -> int:
    import jax
    import numpy as np
    from flax.core import meta
    from unittest import mock

    from benchmarks.kinds.train import resolve

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="xing4.0-29b-a4b")
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--seed0", type=int, default=2 ** 31 + 54)
    parser.add_argument("--skip-grads", action="store_true")
    parser.add_argument("--skip-loss", action="store_true")
    parser.add_argument("--only", default="", metavar="A,B",
                        help="run these controls alone (default: all)")
    parser.add_argument("--weight-keys", type=int, default=1, metavar="N",
                        help="the gradients over N draws of the check's "
                             "weights, PRNGKey(1) (the harness's) .. N")
    args = parser.parse_args(argv)
    rehearse = rehearse or {}
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
        **rehearse.get("config_args", {}))
    batch = rehearse.get("batch", assumed["batch"])
    sizes = {"n_layer": base.num_layers, "n_head": base.num_heads,
             "ln_eps": assumed["program_layer_norm_epsilon"]}
    ref_kw = rehearse.get("ref_kw", {})
    variants = breakages(program, base)
    if args.only:
        variants = {k: v for k, v in variants.items()
                    if k in args.only.split(",")}
    every = {"sound": (base, False, {}, []), **variants}

    def traced(fn, patches):
        """``fn`` jitted; a variant that breaks the program does so
        while it is traced."""
        jitted = jax.jit(fn)

        def call(*a):
            with contextlib.ExitStack() as stack:
                for module, name, stand_in in patches:
                    stack.enter_context(
                        mock.patch.object(module, name, stand_in))
                return jitted(*a)
        return call

    def to_bf16(tree):
        # an astype round trip inside one jit is dropped on the chip
        # (PERF.md, PR 27): reduce_precision computes in bfloat16 there
        return jax.jit(lambda t: jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, 8, 7), t))(tree)

    def tree_for(cfg, key):
        shapes = meta.unbox(ref.expand_layers(jax.eval_shape(
            lambda: Model(dataclasses.replace(
                cfg, **{entry["depth_arg"]: 1})).init_params(key, batch=1)),
            cfg.num_layers))
        return jax.jit(lambda k: ref.init_like(shapes, k))(key)

    # ---- the loss: full depth, a sequence at a time as the harness
    own_sum = jax.jit(lambda p, t: ref.loss_sum(p, t, **sizes, **ref_kw))
    gaps_of = jax.jit(lambda p, t, c: [
        (d.sum(), g.max()) for d, g in ref.flip_gaps(
            p, t, c, **sizes, **ref_kw)])
    loss_of = {name: traced(lambda p, t, cfg=cfg, kw=kw: program.loss_fn(
        Model(cfg), p, t, **kw), patches)
        for name, (cfg, _, kw, patches) in every.items()}

    # ---- the gradients, as the harness's gradient_check: depth 2, two
    # sequences, weights from PRNGKey(1)
    depth = min(2, base.num_layers)
    gsizes = dict(sizes, n_layer=depth)
    shallow = dataclasses.replace(base, **{entry["depth_arg"]: depth})

    def at_depth(cfg):
        return Model(dataclasses.replace(cfg, **{entry["depth_arg"]: depth}))

    g_ref_of = jax.jit(jax.grad(lambda q, t: ref.loss(
        q, t, **gsizes, **ref_kw)))
    grad_of = {name: traced(jax.grad(
        lambda q, t, cfg=cfg, kw=kw: paired_loss(
            at_depth(cfg), q, t, arch=ref_kw.get("arch"),
            with_misrouted=True, **kw), has_aux=True), patches)
        for name, (cfg, _, kw, patches) in every.items()}
    grad_own = jax.jit(jax.grad(lambda q, t: program.loss_fn(
        at_depth(base), q, t)))
    error = jax.jit(ref.grad_error)

    def hc_leaves(*trees):
        """The hyper-connections' own leaves (``phi``, ``b``, ``gates``)
        of gradient trees: what only the coefficients reach."""
        return [[leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
            tree) if any(getattr(k, "key", None) == "hc" for k in path)]
            for tree in trees]

    ok = True
    for seed in range(args.seed0, args.seed0 + args.seeds):
        line = {"seed": seed, "loss_rtol": ref.LOSS_RTOL,
                "grad_rtol": ref.GRAD_RTOL}
        line.update({name: {} for name in variants})
        if not args.skip_loss:
            params = tree_for(base, jax.random.PRNGKey(seed % (2 ** 31)))
            rounded = to_bf16(params)
            tokens = np.random.default_rng(seed).integers(
                0, base.vocab_size, (batch, base.max_seq_len),
                dtype=np.int32)
            choices = program.router_choices(Model(base), params, tokens)
            stats = program.router_stats(Model(base), params, tokens)
            lanes = program.hc_stats(Model(base), params, tokens)
            per_seq = base.max_seq_len
            own = 0.0
            flips = [0] * base.num_layers
            gap = [0.0] * base.num_layers
            for i in range(batch):
                row = tokens[i:i + 1]
                mine = [c[i * per_seq:(i + 1) * per_seq] for c in choices]
                own += float(own_sum(params, row))
                for n, (d, g) in enumerate(gaps_of(params, row, mine)):
                    flips[n] += int(d)
                    gap[n] = max(gap[n], float(g))
            own /= batch * (per_seq - 1)
            loss = float(loss_of["sound"](params, tokens))
            line.update({
                "ref_loss": own, "loss": loss,
                "loss_err": abs(loss - own) / abs(own),
                "topk_flips_per_layer": flips,
                "flip_score_gap_max_per_layer": gap,
                "landed_share_per_layer": [
                    float(x) for x in stats["landed_share"]],
                "hc": {k: [float(x) for x in v] for k, v in lanes.items()},
                "tokens": batch * per_seq})
            for name, (_, low, _, _) in variants.items():
                line[name]["loss_err"] = abs(float(loss_of[name](
                    rounded if low else params, tokens)) - own) / abs(own)
            print(f"[controls] losses: {json.dumps(line)}", file=sys.stderr,
                  flush=True)
            del params, rounded, choices, stats, lanes

        # the harness draws the check's weights from PRNGKey(1) whatever
        # the seed; further keys say what another draw would read
        for wkey in range(1, 1 + (0 if args.skip_grads
                                  else args.weight_keys)):
            at = line if wkey == 1 else line.setdefault(
                "weight_keys", {}).setdefault(str(wkey), {})
            gtok = np.random.default_rng(seed + 1).integers(
                0, shallow.vocab_size, (2, shallow.max_seq_len),
                dtype=np.int32)
            gshapes = meta.unbox(jax.eval_shape(lambda: Model(
                shallow).init_params(jax.random.PRNGKey(1), batch=2)))
            gparams = jax.jit(lambda k: ref.init_like(gshapes, k))(
                jax.random.PRNGKey(wkey))
            grounded = to_bf16(gparams)
            g_ref = g_ref_of(gparams, gtok)
            g, share = grad_of["sound"](gparams, gtok)
            at["grad_err"] = float(error(g, g_ref))
            at["grad_err_hc"] = float(error(*hc_leaves(g, g_ref)))
            at["misrouted_share"] = float(share)
            at["grad_err_own_routing"] = float(error(
                grad_own(gparams, gtok), g_ref))
            print(f"[controls] weights {wkey} sound: {at['grad_err']} (own "
                  f"routing {at['grad_err_own_routing']}; the connections' "
                  f"leaves alone {at['grad_err_hc']}), misrouted "
                  f"{at['misrouted_share']}", file=sys.stderr, flush=True)
            del g
            for name, (_, low, _, _) in variants.items():
                g, share = grad_of[name](grounded if low else gparams, gtok)
                got = {"grad_err": float(error(g, g_ref)),
                       "grad_err_hc": float(error(*hc_leaves(g, g_ref))),
                       "misrouted_share": float(share)}
                if wkey == 1:
                    line[name].update(got)
                else:
                    at[name] = got
                del g
                print(f"[controls] weights {wkey} {name}: {got}",
                      file=sys.stderr, flush=True)
            del g_ref, gparams, grounded

        sound = line.get("loss_err", 0.0) <= ref.LOSS_RTOL and \
            line.get("grad_err", 0.0) <= ref.GRAD_RTOL
        others = line.get("weight_keys", {}).values()
        sound = sound and all(
            at["grad_err"] <= ref.GRAD_RTOL for at in others)
        caught = {name: line[name].get("loss_err", 0.0) > ref.LOSS_RTOL
                  or (line[name].get("grad_err", 0.0) > ref.GRAD_RTOL
                      and all(at[name]["grad_err"] > ref.GRAD_RTOL
                              for at in others))
                  for name in variants}
        line["sound"], line["caught"] = sound, caught
        ok = ok and sound and all(caught.values())
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
