"""What the harness's gradient check differentiates as the Nemotron-H
program's loss (``entry.loss_fn`` of ``configs/
nemotron-3-nano-30b-a3b.json``): the program's own ``loss_fn``, every
token routed to the experts the REFERENCE chose, and 0 where the
program's own routing is not the reference's up to near ties.  Why, and
what the two parts of the comparison are: ``afmoe_paired.py``, whose
count of misrouted tokens this is (one routed layer serves all three
models); the reference that does the choosing differs, and the share of
misrouted tokens it allows.

A third part is this model's own, :func:`scan_error`: the program's scan
op against the reference's step-by-step recurrence IN FLOAT32, at the
cell's shapes, on the first mixer's own inputs for the first sequence.
Why: what a rounded carry between chunks does to a bfloat16 step is
noise among noise (independent roundings of a state average out in every
contraction that reads it: at random weights it moves the whole-tree
gradient error in its fourth digit), so neither of the harness's two
numbers can tell a float32 state from a bfloat16 one; with float32
operands the op agrees with the recurrence to rounding, and a carry
rounded to bfloat16, or dropped, does not.  Past ``SCAN_RTOL`` the loss
returned is 0 as well.

The first loss of every run compares the program with its OWN choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import nemotron_h as reference
from benchmarks.reference.afmoe_paired import (  # noqa: F401
    ROUTING_GAP,
    misrouted_share,
)

#: most tokens, of all of all expert layers, that may be misrouted (an
#: expert taken that the reference scores more than ``ROUTING_GAP``
#: below one left out).  On the chip at the cell's size (my chip runs,
#: PR 35): the program 0.10% (0.05% on bfloat16 parameters); a bfloat16
#: router on bfloat16 parameters 5.1%; the norm before the gate 8.8%,
#: ``relu`` for ``relu^2`` in the shared expert 16%, ``D xs`` left out
#: 23%, the convolution a tap ahead or the shared expert left out 27%.
#: The limit lies a factor 10 above the program and 5 below the nearest
#: control
MISROUTED_MAX = 0.01


#: ||program scan - recurrence|| / ||recurrence|| without the skip term,
#: float32 operands, one sequence at the cell's shapes.  On the chip (my
#: chip runs, PR 35, four calls): the kernels 4.313e-5 (what float32
#: leaves of ``exp(cum_t - cum_s)`` where ``cum`` runs to hundreds; 3.9e-6
#: on inputs whose decays stay small).  The carry controls break
#: ``ssd_einsum``, the same algebra in plain jnp (the kernels keep their
#: carry in VMEM, where nothing can be patched): with its carry left
#: sound it reads 4.313e-5 as the kernels do, so what follows is the
#: carry's alone: rounded to bfloat16 at every chunk 3.46e-4; dropped
#: 0.219; decay without ``dt`` (the kernels) 0.706.  The limit lies a
#: factor 2.8 from the kernels and 2.9 from the bfloat16 carry; the
#: probe's weights are ``PRNGKey(1)``'s in every run.  What it does NOT
#: see: the timed step runs the kernels on bfloat16 operands; their
#: carry is covered through this float32-operand call of the same kernel
#: body (the scratch is float32 whatever the operands: ``ops/ssd.py``
#: ``_call``), not by a reading of the timed call itself
SCAN_RTOL = 1.2e-4


def scan_error(model, params, tokens, arch=None) -> jax.Array:
    """The program's scan (``ray_tpu.models.nemotron_h.ssd``, as its
    mixers call it) against ``reference.recurrence`` on what the FIRST
    mixer would be given for the first sequence if it stood first in the
    stack (the embeddings through its norm, projection and convolution),
    everything float32, ``D`` zero on both sides so that the scan's own
    part is what is compared."""
    from ray_tpu.models import nemotron_h as program

    f32 = jnp.float32
    cfg = model.config
    arch = dict(reference.ARCH, **(arch or {}))
    p = jax.tree.map(lambda a: jax.lax.stop_gradient(a).astype(f32),
                     params["m0"]["mixer"])
    with jax.default_matmul_precision("highest"):
        x = jax.lax.stop_gradient(params["embed"]).astype(f32)[tokens[0]]
        _, xs, dt, b, c = reference.scan_inputs(x, p, cfg.rms_eps, arch)
        a, skip = -jnp.exp(p["A_log"]), jnp.zeros_like(p["D"])
        want = reference.recurrence(xs, dt, a, b, c, skip,
                                    min(128, xs.shape[0]))
        got = program.ssd(xs[None], dt[None], a, b[None], c[None], skip,
                          chunk=cfg.chunk)[0]
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(
        want.ravel())


def reference_routing(cfg, params, tokens, arch=None):
    """Per expert layer ``(choice [B*T, k], scores [B*T, N])`` of the
    reference (which runs a sequence at a time itself).  ``arch``: its
    constants where they are not the configuration file's."""
    with jax.default_matmul_precision("highest"):
        return reference.hidden(
            jax.lax.stop_gradient(params), tokens, n_layer=cfg.num_layers,
            n_head=cfg.num_heads, ln_eps=cfg.rms_eps, arch=arch,
            with_scores=True)[2]


def program_loss(model, params, tokens, arch=None, with_misrouted=False,
                 **kw):
    from ray_tpu.models.nemotron_h import loss_fn

    routed = reference_routing(model.config, params, tokens, arch)
    loss, own = loss_fn(model, params, tokens, with_choices=True,
                        choices=[choice for choice, _ in routed], **kw)
    misrouted = misrouted_share(routed, own)
    sound = jnp.logical_and(
        misrouted <= MISROUTED_MAX,
        scan_error(model, params, tokens, arch) <= SCAN_RTOL)
    loss = jnp.where(sound, loss, 0.0)
    return (loss, misrouted) if with_misrouted else loss
