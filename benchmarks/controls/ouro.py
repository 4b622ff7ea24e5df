"""The CONTROLS of the Ouro (Ouro-2.6B) cell's two comparisons, at the
configuration's own size, in one process that holds the chip (no
runtime, no gang: the builder runs it, the benchmark's runs never do):

    python3 benchmarks/controls/ouro.py --seeds 2

For each seed, as ``benchmarks/kinds/train.py`` does it: the weights from
the seed, the first batch, the program's loss against the reference's
(``LOSS_RTOL``), and the gradients of program and reference at depth 2
(2 layers x 4 passes) on two sequences (``GRAD_RTOL``).  Beside the
sound program the controls, each of which has to fail at least one of
the two limits, or the comparison that decides ``correct`` decides
nothing:

* ``three_passes_not_four``   the stack applied three times;
* ``no_norm_between_passes``  a pass reads the state BEFORE the final
  norm (the exits still read the normed one);
* ``post_norms_dropped``      pre-norm only: ``x + f(N(x))``;
* ``exits_weighted_evenly``   ``p_t = 1/4`` whatever the gate says;
* ``gate_detached``           no gradient through ``p``;
* ``no_entropy_term``         ``beta = 0``;
* ``bf16_head_logits``        the ``[chunk, V]`` logits in bfloat16;
* ``bf16_exit_distribution``  ``log p`` and ``p`` rounded to bfloat16;
* ``lower_precision``         every float32 the configuration states
  lowered to bfloat16 (parameters, head logits, exit distribution): the
  nearest precision below.

NOTHING IS PATCHED.  A control is another configuration value, a
subclass that stands in for one of the program's class attributes
(``Ouro.Block``, ``OuroBlock.parts``, ``_Part.sandwich``,
``Ouro.reads``), a loss put together from the program's own
``exit_terms``, or rounded parameters; :func:`main` takes its
``breakages`` as an argument.  One JSON line a seed; exit code 0 only if
every sound comparison held and every control failed one.  ``rehearse``
(tests): tiny sizes, CPU."""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys
from typing import Any, Callable, Dict, NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "ouro-2.6b"


class Variant(NamedTuple):
    """One program to hold against the reference."""
    model: Any          # the module's class
    config: Any
    rounded: bool       # its parameters rounded to bfloat16
    loss: Callable      # (model, params, tokens) -> mean loss


def breakages(program, base) -> Dict[str, Variant]:
    import jax
    import jax.numpy as jnp

    def bf16(x):
        # an astype round trip inside one jit is dropped on the chip
        # (PERF.md, PR 27): reduce_precision computes in bfloat16 there
        return jax.lax.reduce_precision(x, 8, 7)

    def pre_norm_only(part):
        class PreNormOnly(part):
            def sandwich(self, x, inner, name):
                eps = self.config.rms_eps
                out = inner(program.RMSNorm(eps, name=name + "_norm")(x))
                # the parameter stays, the norm goes
                program.RMSNorm(eps, name=name + "_post_norm")(out)
                return x + out
        return PreNormOnly

    class PreNormBlock(program.OuroBlock):
        parts = tuple(pre_norm_only(p) for p in program.OuroBlock.parts)

    class PreNormOuro(program.Ouro):
        Block = PreNormBlock

    class RawBetweenPasses(program.Ouro):
        @staticmethod
        def reads(raw, normed):
            return raw

    def weighted(weigh, **kw):
        """The program's loss with another weighting of its exits."""
        def loss(model, params, tokens):
            ce, gate = program.exit_terms(model, params, tokens, **kw)
            return weigh(ce, gate, model.config.exit_beta).mean()
        return loss

    def evenly(ce, gate, beta):
        return ce.mean(0) - beta * jnp.log(float(ce.shape[0]))

    def detached(ce, gate, beta):
        return program.exit_loss(ce, jax.lax.stop_gradient(gate), beta)

    def rounded_p(ce, gate, beta):
        log_p = bf16(program.exit_log_p(gate))
        return jnp.sum(bf16(jnp.exp(log_p)) * (ce + beta * log_p), axis=0)

    sound, replace = program.loss_fn, dataclasses.replace
    return {
        "three_passes_not_four": Variant(
            program.Ouro, replace(base, passes=base.passes - 1), False,
            sound),
        "no_norm_between_passes": Variant(RawBetweenPasses, base, False,
                                          sound),
        "post_norms_dropped": Variant(PreNormOuro, base, False, sound),
        "exits_weighted_evenly": Variant(program.Ouro, base, False,
                                         weighted(evenly)),
        "gate_detached": Variant(program.Ouro, base, False,
                                 weighted(detached)),
        "no_entropy_term": Variant(
            program.Ouro, replace(base, exit_beta=0.0), False, sound),
        "bf16_head_logits": Variant(
            program.Ouro, base, False,
            lambda m, p, t: sound(m, p, t, head_logits_dtype=jnp.bfloat16)),
        "bf16_exit_distribution": Variant(program.Ouro, base, False,
                                          weighted(rounded_p)),
        "lower_precision": Variant(
            program.Ouro, base, True,
            weighted(rounded_p, head_logits_dtype=jnp.bfloat16)),
    }


def main(argv=None, rehearse=None, breakages=breakages) -> int:
    import jax
    import numpy as np
    from flax.core import meta

    from benchmarks.kinds.train import resolve

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--seed0", type=int, default=2 ** 31 + 47)
    parser.add_argument("--skip-grads", action="store_true")
    parser.add_argument("--skip-loss", action="store_true")
    parser.add_argument("--only", default="", metavar="A,B",
                        help="run these controls alone (default: all)")
    args = parser.parse_args(argv)
    rehearse = rehearse or {}
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        conf = json.load(f)
    entry, assumed = conf["entry"], conf["assumed"]
    ref = importlib.import_module(conf["reference"])
    program = importlib.import_module(resolve(entry["model"]).__module__)
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
    every = {"sound": Variant(resolve(entry["model"]), base, False,
                              resolve(entry["loss_fn"])), **variants}
    depth = min(2, base.num_layers)

    def at_depth(v: Variant, n: int):
        return v.model(dataclasses.replace(
            v.config, **{entry["depth_arg"]: n}))

    to_bf16 = jax.jit(lambda tree: jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 8, 7), tree))

    def tree_for(n_layer, key, n_seq):
        shapes = meta.unbox(ref.expand_layers(jax.eval_shape(
            lambda: at_depth(every["sound"], 1).init_params(
                key, batch=n_seq)), n_layer))
        return jax.jit(lambda k: ref.init_like(shapes, k))(key)

    # ---- the loss: full depth, the reference a sequence at a time
    own_sum = jax.jit(lambda p, t: ref.loss_sum(p, t, **sizes, **ref_kw))
    loss_of = {name: jax.jit(
        lambda p, t, v=v: v.loss(at_depth(v, base.num_layers), p, t))
        for name, v in every.items()}
    # ---- the gradients, as the harness's gradient_check: depth 2, two
    # sequences, weights from PRNGKey(1)
    gsizes = dict(sizes, n_layer=depth)
    g_ref_of = jax.jit(jax.grad(lambda q, t: ref.loss(
        q, t, **gsizes, **ref_kw)))
    grad_of = {name: jax.jit(jax.grad(
        lambda q, t, v=v: v.loss(at_depth(v, depth), q, t)))
        for name, v in every.items()}
    error = jax.jit(ref.grad_error)

    ok = True
    for seed in range(args.seed0, args.seed0 + args.seeds):
        line: Dict[str, Any] = {"seed": seed, "loss_rtol": ref.LOSS_RTOL,
                                "grad_rtol": ref.GRAD_RTOL}
        line.update({name: {} for name in variants})
        if not args.skip_loss:
            params = tree_for(base.num_layers,
                              jax.random.PRNGKey(seed % (2 ** 31)), 1)
            rounded = to_bf16(params)
            tokens = np.random.default_rng(seed).integers(
                0, base.vocab_size, (batch, base.max_seq_len),
                dtype=np.int32)
            own = sum(float(own_sum(params, tokens[i:i + 1]))
                      for i in range(batch)) \
                / (batch * (base.max_seq_len - 1))
            for name, v in every.items():
                loss = float(loss_of[name](
                    rounded if v.rounded else params, tokens))
                err = abs(loss - own) / abs(own)
                if name == "sound":
                    line.update(ref_loss=own, loss=loss, loss_err=err)
                else:
                    line[name]["loss_err"] = err
            print(f"[controls] losses: {json.dumps(line)}", file=sys.stderr,
                  flush=True)
            del params, rounded

        if not args.skip_grads:
            gtok = np.random.default_rng(seed + 1).integers(
                0, base.vocab_size, (2, base.max_seq_len), dtype=np.int32)
            gparams = tree_for(depth, jax.random.PRNGKey(1), 2)
            grounded = to_bf16(gparams)
            g_ref = g_ref_of(gparams, gtok)
            for name, v in every.items():
                g = grad_of[name](grounded if v.rounded else gparams, gtok)
                err = float(error(g, g_ref))
                del g
                if name == "sound":
                    line["grad_err"] = err
                else:
                    line[name]["grad_err"] = err
                print(f"[controls] {name}: grad_err {err}", file=sys.stderr,
                      flush=True)
            del g_ref, gparams, grounded

        sound = line.get("loss_err", 0.0) <= ref.LOSS_RTOL and \
            line.get("grad_err", 0.0) <= ref.GRAD_RTOL
        caught = {name: line[name].get("loss_err", 0.0) > ref.LOSS_RTOL
                  or line[name].get("grad_err", 0.0) > ref.GRAD_RTOL
                  for name in variants}
        line["sound"], line["caught"] = sound, caught
        ok = ok and sound and all(caught.values())
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
