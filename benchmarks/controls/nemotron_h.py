"""The CONTROLS of the Nemotron-H (Nemotron-3-Nano-30B-A3B) cell's two
comparisons, at the configuration's own size, in one process that holds
the chip (no runtime, no gang: the builder runs it, the benchmark's runs
never do):

    python3 benchmarks/controls/nemotron_h.py --seeds 2

The flow is ``controls/deepseek_v3.py``'s ``main`` (one routed layer and
one harness serve both cells: the weights from the seed, the first loss
against the reference's under ``LOSS_RTOL``, the paired gradients at
depth 2 on two sequences under ``GRAD_RTOL``, the flips, the share of
choices that land on the held experts) over THIS cell's configuration
and THIS file's breakages, each of which has to fail at least one of the
two limits, or the comparison that decides ``correct`` decides nothing:

* ``bf16_params``         the parameters rounded to bfloat16;
* ``lower_precision``     every float32 the configuration states lowered
  to bfloat16 (parameters, router, head logits, the scan's carry): the
  nearest precision below;
* ``no_carry``            the state that enters a chunk dropped: every
  chunk starts from zero;
* ``bf16_carry``          the carry between chunks rounded to bfloat16
  at every chunk;
* ``decay_without_dt``    a step decays by ``exp(A)``, not ``exp(dt A)``;
* ``no_skip``             ``D xs`` left out;
* ``norm_before_gate``    ``RMSNorm(y) * silu(z)`` in place of
  ``RMSNorm(y * silu(z))``;
* ``conv_reads_future``   the convolution shifted a tap: position ``t``
  reads ``t - 2 .. t + 1``;
* ``relu_not_squared``    ``relu`` for ``relu^2`` in the shared expert;
* ``no_shared_expert``    the shared expert left out;
* ``rotary_attention``    rotary positions applied to q and k.

A control breaks the PROGRAM while it is traced (a patched name of
``ray_tpu.models.nemotron_h``, another configuration value, rounded
parameters): the program has no such modes.  The two that reach into
the scan stand in for ``ssd`` with the same chunked algebra in plain
``jnp`` (``ops.ssd.ssd_einsum``) and break the carry there: the kernels
keep theirs in VMEM, where nothing can be patched.  One JSON line a
seed; exit code 0 only if every sound comparison held and every control
failed one.  ``rehearse`` (tests): tiny sizes, CPU."""

from __future__ import annotations

import dataclasses
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "nemotron-3-nano-30b-a3b"


def breakages(program, base):
    """name -> (configuration, rounded parameters?, loss_fn keywords,
    [(attribute of the program's module, what stands in for it)])."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.afmoe import _rope
    from ray_tpu.ops import ssd as scan

    real_ssd, real_flash, dense = (program.ssd, program.flash_attention,
                                   program._dense)
    real_carry = scan._carry

    def einsum_with(carry):
        """The chunked algebra in plain jnp, its carry replaced."""
        def ssd(xs, dt, a, b, c, skip, *, chunk, **_):
            with mock.patch.object(scan, "_carry", carry):
                return scan.ssd_einsum(xs, dt, a, b, c, skip, chunk=chunk)
        return ssd

    def carry_bf16(states, decay, reverse=False):
        def step(s, inp):
            s_c, f_c = inp
            new = f_c[..., None, None] * s + s_c
            # an astype round trip inside one jit is dropped on the chip
            # (PERF.md, PR 27): reduce_precision rounds there too
            return jax.lax.reduce_precision(new, 8, 7), s
        swap = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
        _, entering = jax.lax.scan(step, jnp.zeros_like(states[:, 0]),
                                   (swap(states), swap(decay)))
        return swap(entering)

    def decay_without_dt(xs, dt, a, b, c, skip, **kw):
        y = real_ssd(xs * dt.astype(xs.dtype)[..., None], jnp.ones_like(dt),
                     a, b, c, jnp.zeros_like(skip), **kw)
        return y + (skip[:, None] * xs).astype(y.dtype)

    def no_skip(xs, dt, a, b, c, skip, **kw):
        return real_ssd(xs, dt, a, b, c, jnp.zeros_like(skip), **kw)

    def norm_before_gate(y, z, scale, groups, eps):
        y = y.astype(jnp.float32)
        parts = y.reshape(*y.shape[:-1], groups, -1)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(jnp.square(parts), -1, keepdims=True) + eps)
        return parts.reshape(y.shape) * scale * nn.silu(
            z.astype(jnp.float32))

    def conv_reads_future(u, w, bias):
        taps, seq = w.shape[0], u.shape[1]
        padded = jnp.pad(u.astype(jnp.float32),
                         ((0, 0), (taps - 2, 1), (0, 0)))
        return nn.silu(bias + sum(w[j] * padded[:, j:j + seq]
                                  for j in range(taps)))

    def shared(act):
        def relu2(cfg, h, width, prefix):
            up = dense(cfg, width, prefix + "up", ("embed", "mlp"))(h)
            return dense(cfg, cfg.embed_dim, prefix + "down",
                         ("mlp", "embed"))(act(up))
        return relu2

    def rotary_flash(q, k, v, **kw):
        return real_flash(_rope(q, 10000.0), _rope(k, 10000.0), v, **kw)

    lower = dataclasses.replace(base, router_dtype=jnp.bfloat16)
    return {
        "bf16_params": (base, True, {}, []),
        "lower_precision": (lower, True, {"head_logits_dtype": jnp.bfloat16},
                            [("ssd", einsum_with(carry_bf16))]),
        "no_carry": (base, False, {}, [("ssd", einsum_with(
            lambda states, decay, reverse=False: jnp.zeros_like(states)))]),
        "bf16_carry": (base, False, {},
                       [("ssd", einsum_with(carry_bf16))]),
        "decay_without_dt": (base, False, {}, [("ssd", decay_without_dt)]),
        "no_skip": (base, False, {}, [("ssd", no_skip)]),
        "norm_before_gate": (base, False, {},
                             [("gated_group_norm", norm_before_gate)]),
        "conv_reads_future": (base, False, {},
                              [("causal_conv", conv_reads_future)]),
        "relu_not_squared": (base, False, {}, [("_relu2", shared(nn.relu))]),
        "no_shared_expert": (base, False, {},
                             [("_relu2", shared(lambda up: 0.0 * up))]),
        "rotary_attention": (base, False, {},
                             [("flash_attention", rotary_flash)]),
    }


def scan_readings(rehearse=None):
    """``nemotron_h_paired.scan_error`` of the sound program and of every
    control that stands in for ``ssd``, on stderr: the third part of the
    paired comparison, which the shared flow's lines do not carry (a
    reading past ``SCAN_RTOL`` shows there as a gradient error of 1).
    ``einsum_sound_carry`` is no control: the carry controls break
    ``ssd_einsum``, not the kernels, so its reading with the carry left
    sound says how much of theirs is the stand-in's own."""
    import contextlib
    import json

    import jax
    import numpy as np
    from flax.core import meta

    from benchmarks.kinds.train import resolve
    from benchmarks.reference import nemotron_h as ref
    from benchmarks.reference import nemotron_h_paired as paired
    from ray_tpu.models import nemotron_h as program
    from ray_tpu.ops import ssd as scan

    def einsum(xs, dt, a, b, c, skip, *, chunk, **_):
        return scan.ssd_einsum(xs, dt, a, b, c, skip, chunk=chunk)

    rehearse = rehearse or {}
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        entry = json.load(f)["entry"]
    base = dataclasses.replace(
        resolve(entry["config"])(**entry["config_args"]),
        **{**rehearse.get("config_args", {}), entry["depth_arg"]: 1})
    model = program.NemotronH(base)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(1), batch=1)))
    params = jax.jit(lambda k: ref.init_like(shapes, k))(
        jax.random.PRNGKey(1))
    tokens = np.random.default_rng(1).integers(
        0, base.vocab_size, (1, base.max_seq_len), dtype=np.int32)
    arch = rehearse.get("ref_kw", {}).get("arch")
    out = {}
    for name, (_, _, _, patches) in {
            "sound": (base, False, {}, []),
            "einsum_sound_carry": (base, False, {}, [("ssd", einsum)]),
            **breakages(program, base)}.items():
        if name != "sound" and not any(n == "ssd" for n, _ in patches):
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
    return out


def main(argv=None, rehearse=None) -> int:
    from benchmarks.controls import deepseek_v3 as flow

    argv = list(sys.argv[1:] if argv is None else argv)
    if "--config" not in argv:
        argv += ["--config", CONFIG]
    scan_readings(rehearse)
    with mock.patch.object(flow, "breakages", breakages):
        return flow.main(argv, rehearse)


if __name__ == "__main__":
    sys.exit(main())
