"""The CONTROLS of the Qwen3-Next (Qwen3-Next-80B-A3B-Instruct) cell's two
comparisons, at the configuration's own size, in one process that holds
the chip (no runtime, no gang: the builder runs it, the benchmark's runs
never do):

    python3 benchmarks/controls/qwen3_next.py --seeds 2

The flow is ``controls/deepseek_v3.py``'s ``main`` (the weights from the
seed, the first loss against the reference's under ``LOSS_RTOL``, the
paired gradients at depth 2, ``L L F``, on two sequences under
``GRAD_RTOL``, the flips, the share of choices that land on the held
experts), copied here because that one counts a stack's expert layers by
its depth argument and this stack has one more (``num_expert_layers``;
PERF.md section 7), over THIS cell's configuration and THIS file's
breakages, each of which has to fail at least one of the two limits, or
the comparison that decides ``correct`` decides nothing:

* ``bf16_params``         the parameters rounded to bfloat16;
* ``lower_precision``     every float32 the configuration states lowered
  to bfloat16 (parameters, router, head logits, ``g``, ``beta`` and the
  scan's carry): the nearest precision below;
* ``no_carry``            the state that enters a chunk dropped: every
  chunk starts from zero;
* ``bf16_carry``          the carry rounded to bfloat16 at every chunk;
* ``no_decay``            ``alpha = 1``: the state is never decayed;
* ``no_correction``       ``d_t = beta_t v_t``: plain gated linear
  attention, nothing taken out of the state;
* ``beta_one``            ``beta = 1``;
* ``no_l2_norm``          q and k not l2-normalised (q still scaled);
* ``gate_before_norm``    ``w_n * rms(o * silu(z))``;
* ``conv_reads_future``   the convolution shifted a tap: position ``t``
  reads ``t - 2 .. t + 1``;
* ``no_output_gate``      attention's output not gated;
* ``rotary_all``          the rotation over all 256 elements of a head;
* ``norm_scale_w``        a norm's scale ``w`` for ``1 + w``;
* ``shared_ungated``      the shared expert's result not weighed;
* ``sigmoid_router``      a sigmoid for the softmax in the router.

A control breaks the PROGRAM while it is traced (a patched name of
``ray_tpu.models.qwen3_next``, another configuration value, rounded
parameters): the program has no such modes.  The carry controls wrap
``lax.scan`` around the program's own scan op while it is traced, so the
chunk's body is the op's own.  One JSON line a seed; exit code 0 only if
every sound comparison held and every control failed one.  ``rehearse``
(tests): tiny sizes, CPU."""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "qwen3-next-80b-a3b"


def _round(x):
    """To bfloat16's precision in ``x``'s dtype: an astype round trip
    inside one jit is dropped on the chip (PERF.md, PR 27)."""
    import jax

    return jax.lax.reduce_precision(x, 8, 7)


def breakages(program, base):
    """name -> (configuration, rounded parameters?, loss_fn keywords,
    [(attribute of the program's module, what stands in for it)])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import gated_delta as gd
    from ray_tpu.ops.fused import _rmsnorm_ref

    real_scan, real_op = jax.lax.scan, program.gated_delta
    real_norm = program.fused_rmsnorm

    def with_carry(entering, gates=lambda x: x):
        """The program's scan op, the state that enters each chunk
        passed through ``entering``, ``g`` and ``beta`` through
        ``gates``."""
        def scan(body, init, xs, **kw):
            return real_scan(lambda s, x: body(entering(s), x), init, xs,
                             **kw)

        def op(q, k, v, g, beta, **kw):
            # (the op's body outside its inner ``jit``, whose cache
            # would answer with the sound program's trace)
            with mock.patch.object(gd.jax.lax, "scan", scan), \
                    mock.patch.object(gd, "_gated_delta",
                                      gd._gated_delta.__wrapped__):
                return real_op(q, k, v, gates(g), gates(beta), **kw)
        return op

    def no_correction(q, k, v, g, beta, *, chunk):
        """``S <- alpha S + k (beta v)^T``, ``o = S^T q``, step by step
        (segments of ``chunk`` steps under ``jax.checkpoint``)."""
        f32 = jnp.float32
        b, t, hv, dv = v.shape
        rep = hv // k.shape[2]

        def step(S, inp):
            qt, kt, vt, gt, bt = inp
            qt, kt = jnp.repeat(qt, rep, 1), jnp.repeat(kt, rep, 1)
            S = jnp.exp(gt)[..., None, None] * S \
                + kt[..., :, None] * (bt[..., None] * vt)[..., None, :]
            return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

        def cut(a):
            a = jnp.moveaxis(a.astype(f32), 1, 0)
            return a.reshape(t // chunk, chunk, *a.shape[1:])

        first = jnp.zeros((b, hv, k.shape[3], dv), f32)
        _, o = real_scan(jax.checkpoint(
            lambda S, inp: real_scan(step, S, inp)), first,
            tuple(map(cut, (q, k, v, g, beta))))
        return jnp.moveaxis(o.reshape(t, b, hv, dv), 0, 1).astype(v.dtype)

    def gate_before_norm(o, z, scale, eps):
        f = o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        f = f * jax.lax.rsqrt(jnp.mean(f * f, -1, keepdims=True) + eps)
        return (scale.astype(jnp.float32) * f).astype(o.dtype)

    def conv_reads_future(u, w, bias=None):
        taps, seq = w.shape[0], u.shape[1]
        padded = jnp.pad(u.astype(jnp.float32),
                         ((0, 0), (taps - 2, 1), (0, 0)))
        return jax.nn.silu(sum(w[j] * padded[:, j:j + seq]
                               for j in range(taps))).astype(u.dtype)

    lower = dataclasses.replace(base, router_dtype=jnp.bfloat16)
    patched = lambda *pairs: (base, False, {}, list(pairs))  # noqa: E731
    return {
        "bf16_params": (base, True, {}, []),
        "lower_precision": (lower, True, {"head_logits_dtype": jnp.bfloat16},
                            [("gated_delta", with_carry(_round, _round))]),
        "no_carry": patched(("gated_delta", with_carry(jnp.zeros_like))),
        "bf16_carry": patched(("gated_delta", with_carry(_round))),
        "no_decay": patched(("gated_delta", with_carry(
            lambda s: s, lambda x: jnp.where(x < 0, 0.0, x)))),
        "no_correction": patched(("gated_delta", no_correction)),
        "beta_one": patched(("gated_delta", lambda q, k, v, g, beta, **kw:
                             real_op(q, k, v, g, jnp.ones_like(beta),
                                     **kw))),
        "no_l2_norm": patched(("l2_normalised", lambda x, scale=1.0: (
            x.astype(jnp.float32) * scale).astype(x.dtype))),
        "gate_before_norm": patched(("norm_then_gate", gate_before_norm)),
        "conv_reads_future": patched(("short_conv", conv_reads_future)),
        "no_output_gate": patched(("output_gated", lambda attn, gate: attn)),
        "rotary_all": (dataclasses.replace(
            base, rotary_dim=base.head_dim), False, {}, []),
        "norm_scale_w": patched(
            ("fused_rmsnorm", lambda x, w, *, eps, offset:
             real_norm(x, w, eps=eps)),
            ("_rmsnorm_ref", lambda x, w, eps, offset=0.0:
             _rmsnorm_ref(x, w, eps))),
        "shared_ungated": patched(("shared_weight", jnp.ones_like)),
        "sigmoid_router": (dataclasses.replace(
            base, score_func="sigmoid"), False, {}, []),
    }


def scan_readings(rehearse=None):
    """``qwen3_next_paired.scan_error`` of the sound program and of every
    control that stands in for the scan op, and ``route_error`` of the
    sound program and of every control that is another configuration, on
    stderr: the third and fourth part of the paired comparison, which the
    flow's lines do not carry (a reading past its limit shows there as a
    gradient error of 1)."""
    import jax
    import numpy as np
    from flax.core import meta

    from benchmarks.kinds.train import resolve
    from benchmarks.reference import qwen3_next as ref
    from benchmarks.reference import qwen3_next_paired as paired
    from ray_tpu.models import qwen3_next as program

    rehearse = rehearse or {}
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        entry = json.load(f)["entry"]
    base = dataclasses.replace(
        resolve(entry["config"])(**entry["config_args"]),
        **{**rehearse.get("config_args", {}), entry["depth_arg"]: 1})
    model = program.Qwen3Next(base)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(1), batch=1)))
    params = jax.jit(lambda k: ref.init_like(shapes, k))(
        jax.random.PRNGKey(1))
    tokens = np.random.default_rng(1).integers(
        0, base.vocab_size, (1, base.max_seq_len), dtype=np.int32)
    arch = rehearse.get("ref_kw", {}).get("arch")
    out, routes = {}, {}
    for name, (cfg, _, _, patches) in {"sound": (base, False, {}, []),
                                       **breakages(program, base)}.items():
        if name == "sound" or cfg != base:   # the router's own probe
            routes[name] = float(jax.jit(
                lambda p, t, cfg=cfg: paired.route_error(
                    program.Qwen3Next(cfg), p, t, arch))(params, tokens))
        if name != "sound" and not any(n == "gated_delta"
                                       for n, _ in patches):
            continue
        with contextlib.ExitStack() as stack:
            for attr, stand_in in patches:
                stack.enter_context(
                    mock.patch.object(program, attr, stand_in))
            out[name] = float(jax.jit(
                lambda p, t: paired.scan_error(model, p, t, arch))(
                    params, tokens))
    print(f"[controls] scan_error (limit {paired.SCAN_RTOL}): "
          f"{json.dumps(out)}", file=sys.stderr, flush=True)
    print(f"[controls] route_error (limit {paired.ROUTE_RTOL}): "
          f"{json.dumps(routes)}", file=sys.stderr, flush=True)
    return dict(out, route_error=routes)


def main(argv=None, rehearse=None) -> int:
    import jax
    import numpy as np
    from flax.core import meta

    from benchmarks.kinds.train import resolve

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--seed0", type=int, default=2 ** 31 + 33)
    parser.add_argument("--skip-grads", action="store_true")
    parser.add_argument("--skip-loss", action="store_true")
    parser.add_argument("--skip-scan", action="store_true")
    parser.add_argument("--only", default="", metavar="A,B",
                        help="run these controls alone (default: all)")
    args = parser.parse_args(sys.argv[1:] if argv is None else argv)
    rehearse = rehearse or {}
    if not args.skip_scan:
        scan_readings(rehearse)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
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
                for name, stand_in in patches:
                    stack.enter_context(
                        mock.patch.object(program, name, stand_in))
                return jitted(*a)
        return call

    to_bf16 = jax.jit(lambda t: jax.tree.map(_round, t))

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
    # (a variant's programs are built when they are read and dropped
    # after: sixteen of them held at once passed the one-chip machine's
    # 40 GiB of host memory, my chip run, PR 58, call 3)
    def loss_of(name):
        cfg, _, kw, patches = every[name]
        return traced(lambda p, t: program.loss_fn(Model(cfg), p, t, **kw),
                      patches)

    # ---- the gradients, as the harness's gradient_check: depth 2, two
    # sequences, weights from PRNGKey(1)
    depth = min(2, base.num_layers)
    gsizes = dict(sizes, n_layer=depth)
    shallow = dataclasses.replace(base, **{entry["depth_arg"]: depth})

    def at_depth(cfg):
        return Model(dataclasses.replace(cfg, **{entry["depth_arg"]: depth}))

    g_ref_of = jax.jit(jax.grad(lambda q, t: ref.loss(
        q, t, **gsizes, **ref_kw)))
    def grad_of(name):
        """(The loss NOT yet judged, and the probes' readings beside
        it.)"""
        cfg, _, kw, patches = every[name]
        return traced(jax.grad(
            lambda q, t: paired_loss(at_depth(cfg), q, t,
                                     arch=ref_kw.get("arch"),
                                     with_probes=True, **kw),
            has_aux=True), patches)

    paired = importlib.import_module(paired_loss.__module__)

    def read(g, probes, g_ref):
        """A paired gradient's readings: the error as it is (``raw``),
        as the harness reads it (1 where a probe is past its limit),
        and the probes."""
        raw = float(error(g, g_ref))
        out = {k: float(v) for k, v in probes.items()
               if k != "misrouted_at"}
        out["misrouted_at"] = dict(zip(
            map(str, paired.GAPS),
            (float(x) for x in probes["misrouted_at"])))
        judged = raw if bool(paired.sound(probes)) else 1.0
        return {"grad_err": judged, "grad_err_raw": raw,
                "misrouted_share": out.pop("misrouted"), **out}
    grad_own = jax.jit(jax.grad(lambda q, t: program.loss_fn(
        at_depth(base), q, t)))
    error = jax.jit(ref.grad_error)

    ok = True
    layers = base.num_expert_layers
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
            per_seq = base.max_seq_len
            own, flips, gap = 0.0, [0] * layers, [0.0] * layers
            for i in range(batch):
                row = tokens[i:i + 1]
                mine = [c[i * per_seq:(i + 1) * per_seq] for c in choices]
                own += float(own_sum(params, row))
                for n, (d, g) in enumerate(gaps_of(params, row, mine)):
                    flips[n] += int(d)
                    gap[n] = max(gap[n], float(g))
            own /= batch * (per_seq - 1)
            loss = float(loss_of("sound")(params, tokens))
            line.update({
                "ref_loss": own, "loss": loss,
                "loss_err": abs(loss - own) / abs(own),
                "topk_flips_per_layer": flips,
                "flip_score_gap_max_per_layer": gap,
                "landed_share_per_layer": [
                    float(x) for x in stats["landed_share"]],
                "imbalance_per_layer": [
                    float(x) for x in stats["imbalance"]],
                "live_tiles_per_layer": [
                    [int(a), int(b)] for a, b in zip(
                        stats["live_tiles"], stats["buffer_tiles"])],
                "tokens": batch * per_seq})
            for name, (_, low, _, _) in variants.items():
                line[name]["loss_err"] = abs(float(loss_of(name)(
                    rounded if low else params, tokens)) - own) / abs(own)
                jax.clear_caches()
            print(f"[controls] losses: {json.dumps(line)}", file=sys.stderr,
                  flush=True)
            del params, rounded, choices, stats

        if not args.skip_grads:
            gtok = np.random.default_rng(seed + 1).integers(
                0, shallow.vocab_size, (2, shallow.max_seq_len),
                dtype=np.int32)
            gshapes = meta.unbox(jax.eval_shape(lambda: Model(
                shallow).init_params(jax.random.PRNGKey(1), batch=2)))
            gparams = jax.jit(lambda k: ref.init_like(gshapes, k))(
                jax.random.PRNGKey(1))
            grounded = to_bf16(gparams)
            g_ref = g_ref_of(gparams, gtok)
            g, probes = grad_of("sound")(gparams, gtok)
            line.update(read(g, probes, g_ref))
            line["grad_err_own_routing"] = float(error(
                grad_own(gparams, gtok), g_ref))
            print(f"[controls] sound: "
                  f"{ {k: line[k] for k in read(g, probes, g_ref)} } (own "
                  f"routing {line['grad_err_own_routing']})",
                  file=sys.stderr, flush=True)
            del g
            for name, (_, low, _, _) in variants.items():
                g, probes = grad_of(name)(grounded if low else gparams,
                                          gtok)
                line[name].update(read(g, probes, g_ref))
                del g
                jax.clear_caches()
                print(f"[controls] {name}: {line[name]}", file=sys.stderr,
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
