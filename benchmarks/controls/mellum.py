"""The CONTROLS of the Mellum cell's two comparisons, at the
configuration's own size, in one process that holds the FOUR chips under
the cell's mesh and preset (no runtime, no gang: the builder runs it,
the benchmark's runs never do):

    python3 benchmarks/controls/mellum.py --seeds 2

For each seed, as ``benchmarks/kinds/train.py`` does it: the weights from
the seed placed by the preset, the first batch split over the chips, the
program's loss against the reference's (``LOSS_RTOL``), and the
gradients of program and reference at depth 2 on four sequences
(``GRAD_RTOL``; the program's at the reference's routing,
``reference/mellum_paired.py``, as ``entry.loss_fn`` has it).  Beside
the sound program: the gradient error WITHOUT the pairing, the share of
the group's pairs that arrived on each chip, and the controls, each of
which has to fail at least one of the two limits, or the comparison that
decides ``correct`` decides nothing.  A gradient error is given twice:
``grad_err`` as the harness reads it (``mellum_paired.judged``: exactly
1 where more tokens are misrouted than ``MISROUTED_MAX``) and
``grad_err_unjudged``, the arithmetic alone, so that one run places
both limits; ``misrouted_by_gap`` is the misrouted share at ``GAPS``
around the ``ROUTING_GAP`` that decides.  The controls:

* ``full_as_sliding``     full layers rotated with the sliding table
  (no YaRN blend, no factor);
* ``no_attention_factor`` YaRN's frequencies, ``attention_factor`` left
  off cos and sin;
* ``window_2048``         sliding layers see 2,048 keys;
* ``sigmoid_router``      sigmoid scores in place of the softmax;
* ``not_renormalised``    the chosen experts' scores as weights, not
  divided by their sum;
* ``no_scatter_sum``      the scatter's sum left out: each chip keeps
  its own experts' part of its own tokens;
* ``bf16_router``         the router's product, softmax and top-8 in
  bfloat16;
* ``bf16_head_logits``    the head's logits stored in bfloat16;
* ``lower_precision``     every float32 the configuration states lowered
  to bfloat16 (parameters, router, head logits): the nearest precision
  below.

``bf16_head_logits`` alone is reported and NOT required to fail
(``UNSEEN``): a logit of unit spread stored in bfloat16 moves a token's
loss by 0.004 either way and the mean over 65,536 tokens by a millionth,
under the sound program's own reading (PERF.md section 6, PR 51, as in
the cells before it: at initial weights the first loss guards a layer,
the head or the labels gone wrong, not their precision); with every
other float32 lowered beside it (``lower_precision``) the gradients see
it.

A control breaks the PROGRAM while it is traced (a patched name of a
module, another configuration value, rounded parameters): the program
has no such modes.  One JSON line a seed; exit code 0 only if every
sound comparison held and every control failed one.  Some two dozen
programs are compiled, each half a minute of the compiler's time and a
few seconds of the interpreter's: every one is traced at the start of
its half of the run and compiled on a thread of its own.  ``rehearse``
(tests): tiny sizes, CPU devices."""

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


#: controls that neither limit can see at the cell's size, and why: the
#: module's docstring
UNSEEN = {"bf16_head_logits"}


def breakages(base):
    """name -> (configuration, rounded parameters?, loss_fn keywords,
    [(module, attribute, what stands in for it)])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import afmoe, mellum
    from ray_tpu.parallel import expert

    real_table, real_route = mellum.rope_table, afmoe.route

    def sliding_table(cfg, kind, seq):
        return real_table(cfg, "sliding", seq)

    def not_renormalised(cfg, h, w_router, chosen=None):
        idx, weights, own = real_route(cfg, h, w_router, chosen)
        scores = jax.nn.softmax(jnp.dot(
            h.astype(cfg.router_dtype), w_router.astype(cfg.router_dtype),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        return idx, jnp.take_along_axis(scores, idx, axis=1).astype(
            jnp.float32), own

    def own_part(x, axis):
        rows = x.shape[0] // jax.lax.axis_size(axis)
        return jax.lax.dynamic_slice_in_dim(
            x, rows * jax.lax.axis_index(axis), rows)

    def replace(**kw):
        return dataclasses.replace(base, **kw)

    return {
        "full_as_sliding": (base, False, {},
                            [(mellum, "rope_table", sliding_table)]),
        "no_attention_factor": (replace(yarn_attention_factor=1.0), False,
                                {}, []),
        "window_2048": (replace(window=2 * base.window), False, {}, []),
        "sigmoid_router": (replace(score_func="sigmoid"), False, {}, []),
        "not_renormalised": (base, False, {},
                             [(afmoe, "route", not_renormalised)]),
        "no_scatter_sum": (base, False, {},
                           [(expert, "scatter_sums", own_part)]),
        "bf16_router": (replace(router_dtype=jnp.bfloat16), False, {}, []),
        "bf16_head_logits": (base, False,
                             {"head_logits_dtype": jnp.bfloat16}, []),
        "lower_precision": (replace(router_dtype=jnp.bfloat16), True,
                            {"head_logits_dtype": jnp.bfloat16}, []),
    }


def verdict(line, loss_rtol: float, grad_rtol: float, misrouted_max: float):
    """``(the sound program inside both limits?, {control: outside
    one?})`` of one seed's readings (a line this script prints) under
    these limits, as the harness decides: a gradient error reads 1
    where more tokens are misrouted than ``misrouted_max``
    (``mellum_paired.judged``: the loss differentiated is then 0)."""
    def outside(read):
        grad = 1.0 if read["misrouted_share"] > misrouted_max \
            else read["grad_err_unjudged"]
        return read["loss_err"] > loss_rtol or grad > grad_rtol
    return not outside(line), {
        name: outside(read) for name, read in line.items()
        if isinstance(read, dict) and "grad_err_unjudged" in read}


#: gaps at which the share of misrouted tokens is REPORTED beside the one
#: that decides (``mellum_paired.ROUTING_GAP``), so that one run says
#: where that limit stands among the readings
GAPS = (1e-4, 2e-4, 3e-4, 5e-4)


def main(argv=None, rehearse=None) -> int:
    import concurrent.futures

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta
    from jax.sharding import NamedSharding, PartitionSpec
    from unittest import mock

    from benchmarks.kinds.train import resolve
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.parallel.mesh import set_global_mesh
    from ray_tpu.parallel.sharding import flax_sharding

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="mellum2-12b-a2.5b")
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--seed0", type=int, default=2 ** 31 + 51)
    args = parser.parse_args(argv)
    rehearse = rehearse or {}
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        conf = json.load(f)
    entry, assumed = conf["entry"], conf["assumed"]
    ref = importlib.import_module(conf["reference"])
    paired = importlib.import_module(entry["loss_fn"].split(":")[0])
    Model = resolve(entry["model"])
    program = importlib.import_module(Model.__module__)
    base = dataclasses.replace(
        resolve(entry["config"])(**entry["config_args"]),
        **rehearse.get("config_args", {}))
    batch = rehearse.get("batch", assumed["batch"])
    chips = conf["chips"]
    rules = resolve(entry["rules"])
    mesh = build_mesh(MeshConfig(**conf["layout"]["mesh"]),
                      devices=jax.devices()[:chips])
    set_global_mesh(mesh)
    sizes = {"n_layer": base.num_layers, "n_head": base.num_heads,
             "ln_eps": assumed["program_layer_norm_epsilon"]}
    ref_kw = rehearse.get("ref_kw", {})
    variants = breakages(base)
    every = {"sound": (base, False, {}, []), **variants}
    seeds = range(args.seed0, args.seed0 + args.seeds)
    lines = {seed: {"seed": seed, "loss_rtol": ref.LOSS_RTOL,
                    "grad_rtol": ref.GRAD_RTOL,
                    "routing_gap": paired.ROUTING_GAP,
                    "misrouted_max": paired.MISROUTED_MAX,
                    **{name: {} for name in variants}} for seed in seeds}
    compilers = concurrent.futures.ThreadPoolExecutor(
        min(12, os.cpu_count() or 1))

    def said(text):
        print(f"[controls] {text}", file=sys.stderr, flush=True)

    def ahead(fn, given, patches=(), out=None):
        """``fn`` traced and lowered NOW for arguments like ``given`` (a
        variant that breaks the program does so while it is traced) and
        compiled on a thread of its own while the next one is traced
        (a trace holds the interpreter, the compiler does not): the
        compiled program, to come.  A compiled program takes its
        arguments where it was lowered for them and nowhere else, so
        what one program hands the next says where it lies (``out``)."""
        with contextlib.ExitStack() as stack:
            for module, name, stand_in in patches:
                stack.enter_context(
                    mock.patch.object(module, name, stand_in))
            lowered = jax.jit(fn, out_shardings=out).lower(*given)
        return compilers.submit(lowered.compile)

    def where(tree):
        return jax.tree.map(lambda a: a.sharding, tree)

    def to_bf16(tree):
        # an astype round trip inside one jit is dropped on the chip
        # (PERF.md, PR 27): reduce_precision computes in bfloat16 there
        return jax.jit(lambda t: jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, 8, 7), t),
            out_shardings=where(tree))(tree)

    def tree_for(cfg, key):
        """The weights as the harness makes them: the program's tree
        from a depth-1 trace, placed by the preset."""
        boxed = ref.expand_layers(jax.eval_shape(
            lambda: Model(dataclasses.replace(
                cfg, **{entry["depth_arg"]: 1})).init_params(
                    key, batch=chips)), cfg.num_layers)
        shapes, specs = flax_sharding(boxed, rules)
        placed = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        return jax.jit(lambda k: ref.init_like(meta.unbox(shapes), k),
                       out_shardings=placed)(key)

    # ---- the gradients, as the harness's gradient_check: depth 2,
    # max(2, chips) sequences, weights from PRNGKey(1) whatever the
    # seed.  The reference's routing is the same for every variant (its
    # constants are the file's): made once for the weights and once for
    # the rounded ones, and every variant's loss takes it as given
    depth = min(2, base.num_layers)
    n_seq = max(2, chips)
    gsizes = dict(sizes, n_layer=depth)
    shallow = dataclasses.replace(base, **{entry["depth_arg"]: depth})

    def at_depth(cfg):
        return Model(dataclasses.replace(cfg, **{entry["depth_arg"]: depth}))

    def compared(cfg, kw):
        """``(q, t, routed, g_ref) ->`` what the harness's comparison
        reads of this variant: the gradient error as it stands and as
        judged (``mellum_paired.judged``: 1 where the routing is not the
        reference's), the misrouted share at ``GAPS``."""
        def fn(q, t, routed, g_ref):
            def loss(p):
                value, share, own = paired.paired_loss(
                    at_depth(cfg), p, t, routed, **kw)
                return value, (share, own)
            g, (share, own) = jax.grad(loss, has_aux=True)(q)
            return {"grad_err": ref.grad_error(paired.judged(g, share),
                                               g_ref),
                    "grad_err_unjudged": ref.grad_error(g, g_ref),
                    "misrouted_share": share,
                    "misrouted_by_gap": jnp.stack([
                        paired.misrouted_share(routed, own, gap)
                        for gap in GAPS])}
        return fn

    gshapes = meta.unbox(jax.eval_shape(lambda: Model(
        shallow).init_params(jax.random.PRNGKey(1), batch=n_seq)))
    gparams = jax.jit(lambda k: ref.init_like(gshapes, k))(
        jax.random.PRNGKey(1))
    grounded = to_bf16(gparams)
    gtoks = {seed: np.random.default_rng(seed + 1).integers(
        0, shallow.vocab_size, (n_seq, shallow.max_seq_len),
        dtype=np.int32) for seed in seeds}
    gtok = gtoks[seeds[0]]
    whole = NamedSharding(mesh, PartitionSpec())

    def routing(q, t):
        return paired.reference_routing(shallow, q, t, ref_kw.get("arch"))

    g_ref_of = ahead(jax.grad(lambda q, t: ref.loss(
        q, t, **gsizes, **ref_kw)), (gparams, gtok), out=where(gparams))
    routing_of = ahead(routing, (gparams, gtok), out=whole)
    # shapes alone: the variants are traced before the first one runs
    routed_like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole),
        jax.eval_shape(routing, gparams, gtok))
    grad_of = {name: ahead(compared(cfg, kw),
                           (gparams, gtok, routed_like, gparams), patches)
               for name, (cfg, _, kw, patches) in every.items()}
    grad_own = ahead(lambda q, t, g_ref: ref.grad_error(jax.grad(
        lambda p: program.loss_fn(at_depth(base), p, t))(q), g_ref),
        (gparams, gtok, gparams))
    for seed in seeds:
        line, gtok = lines[seed], gtoks[seed]
        g_ref = g_ref_of.result()(gparams, gtok)
        routed = {False: routing_of.result()(gparams, gtok),
                  True: routing_of.result()(grounded, gtok)}
        for name, (_, low, _, _) in every.items():
            got = jax.device_get(grad_of[name].result()(
                grounded if low else gparams, gtok, routed[low], g_ref))
            got = {k: np.asarray(v).tolist() for k, v in got.items()}
            (line if name == "sound" else line[name]).update(got)
            said(f"seed {seed} gradients, {name}: {json.dumps(got)}")
        line["grad_err_own_routing"] = float(
            grad_own.result()(gparams, gtok, g_ref))
        said(f"seed {seed} gradients at the program's own routing: "
             f"{line['grad_err_own_routing']}")
        del g_ref, routed
    del gparams, grounded, g_ref_of, routing_of, grad_of, grad_own

    # ---- the loss: full depth, the reference a sequence at a time
    split = NamedSharding(mesh, rules.spec("batch", None))
    hosts = {seed: np.random.default_rng(seed).integers(
        0, base.vocab_size, (batch, base.max_seq_len), dtype=np.int32)
        for seed in seeds}
    params = tree_for(base, jax.random.PRNGKey(seeds[0] % (2 ** 31)))
    tokens = jax.device_put(hosts[seeds[0]], split)
    own_sum = ahead(lambda p, t: ref.loss_sum(p, t, **sizes, **ref_kw),
                    (params, hosts[seeds[0]][:1]))
    loss_of = {name: ahead(lambda p, t, cfg=cfg, kw=kw: program.loss_fn(
        Model(cfg), p, t, **kw), (params, tokens), patches)
        for name, (cfg, _, kw, patches) in every.items()}
    for seed in seeds:
        line, host = lines[seed], hosts[seed]
        if seed != seeds[0]:
            del params
            params = tree_for(base, jax.random.PRNGKey(seed % (2 ** 31)))
        rounded = to_bf16(params)
        tokens = jax.device_put(host, split)
        stats = program.group_stats(
            Model(base), program.router_stats(
                Model(base), params, tokens), chips, host.size)
        load = np.asarray(stats["load"])
        own = sum(float(own_sum.result()(params, host[i:i + 1]))
                  for i in range(batch)) \
            / (batch * (base.max_seq_len - 1))
        loss = float(loss_of["sound"].result()(params, tokens))
        line.update({
            "ref_loss": own, "loss": loss,
            "loss_err": abs(loss - own) / abs(own),
            "arrived_share_per_layer_per_chip": (
                load.reshape(load.shape[0], chips, -1).sum(-1)
                / load.sum(-1, keepdims=True)).tolist(),
            "exchange_bytes_a_forward": stats["exchange_bytes"],
            "tokens": int(host.size)})
        for name, (_, low, _, _) in variants.items():
            line[name]["loss_err"] = abs(float(loss_of[name].result()(
                rounded if low else params, tokens)) - own) / abs(own)
        said(f"seed {seed} losses: " + json.dumps(
            {"sound": line["loss_err"],
             **{name: line[name]["loss_err"] for name in variants}}))
        del rounded, stats

    ok = True
    for seed in seeds:
        line = lines[seed]
        sound, caught = verdict(line, ref.LOSS_RTOL, ref.GRAD_RTOL,
                                paired.MISROUTED_MAX)
        line["sound"], line["caught"] = sound, caught
        ok = ok and sound and all(
            hit for name, hit in caught.items() if name not in UNSEEN)
        print(json.dumps(line), flush=True)
    set_global_mesh(None)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
