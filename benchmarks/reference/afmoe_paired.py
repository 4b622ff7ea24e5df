"""What the harness's gradient check differentiates as the AFMoE
program's loss (``entry.loss_fn`` of ``configs/trinity-mini.json``): the
program's own ``loss_fn``, every token routed to the experts the
REFERENCE chose, and 0 where the program's own routing is not the
reference's up to near ties.

Why.  A top-k choice is a step in the loss: where a token's eighth and
ninth scores nearly tie, the program's bfloat16 residual stream and the
reference's float32 one pick different experts (4-7% of a layer's tokens
at the cell's size), both rightly, and the two gradients then differ by
that expert's whole part for that token.  That reading says how many
near ties a batch holds, not whether the kernels, the dispatch and the
backward passes are right: it was 0.02 over 32,768 choices on the chip
and 0.06 over 512 in the CPU rehearsal, and one limit for both decided
nothing.  So the comparison is made in two parts, both of which the
harness's one number carries:

* the ROUTING: what the program's routers choose THEMSELVES in that
  same pass (each layer's below the reference's routing of the layers
  before it) against the reference's scores.  A token is misrouted if
  the program took an expert that the reference scores more than
  ``ROUTING_GAP`` below one it left out.  Float32 routers on a bfloat16
  stream misroute one token in a hundred; a router computed in bfloat16
  five times as many, a wrong one most.  Past ``MISROUTED_MAX`` the
  loss returned is the constant 0, its gradient is zero and the
  harness reads a gradient error of exactly 1;
* the ARITHMETIC: both sides take their gradients at ONE routing, the
  reference's own (float32, independent of the program), which the
  program replays through ``loss_fn(choices=)``: the weights are still
  its own scores', and every kernel, gather and backward pass runs as in
  the step.

The first loss of every run compares the program with its OWN choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import afmoe as reference

#: a token is misrouted if the program chose an expert whose reference
#: score (a sigmoid, of order 0.5; bfloat16 keeps 0.002-0.004 of it) is
#: more than this below one it left out
ROUTING_GAP = 1e-3
#: most tokens, of all of all expert layers, that may be misrouted.  On
#: the chip (PERF.md, PR 28, six seeds) the program 1.21-1.33%, with a
#: bfloat16 router 6.33-6.47%; the CPU rehearsal 0-0.8% and 24-28%
MISROUTED_MAX = 0.03


def reference_routing(cfg, params, tokens, arch=None):
    """Per expert layer ``(choice [B*T, k], scores [B*T, N])`` of the
    reference.  ``arch``: its constants where they are not the
    configuration file's (a test's tiny model)."""
    fixed = jax.lax.stop_gradient(params)

    def one(row):   # a sequence at a time: the check runs beside the
        with jax.default_matmul_precision("highest"):   # training state
            return reference.hidden(
                fixed, row[None], n_layer=cfg.num_layers,
                n_head=cfg.num_heads, ln_eps=cfg.rms_eps, arch=arch,
                with_scores=True)[2]

    return [(c.reshape(-1, c.shape[-1]), s.reshape(-1, s.shape[-1]))
            for c, s in jax.lax.map(one, tokens)]


def misrouted_share(routed, own) -> jax.Array:
    """Of all tokens of all expert layers, the share whose ``own``
    choice takes an expert that the reference scores more than
    ``ROUTING_GAP`` below one it leaves out."""
    return jnp.mean(jnp.stack([
        reference.score_gap(scores, choice, theirs) > ROUTING_GAP
        for (choice, scores), theirs in zip(routed, own)]))


def program_loss(model, params, tokens, arch=None, with_misrouted=False,
                 **kw):
    from ray_tpu.models.afmoe import loss_fn

    routed = reference_routing(model.config, params, tokens, arch)
    loss, own = loss_fn(model, params, tokens, with_choices=True,
                        choices=[choice for choice, _ in routed], **kw)
    misrouted = misrouted_share(routed, own)
    loss = jnp.where(misrouted <= MISROUTED_MAX, loss, 0.0)
    return (loss, misrouted) if with_misrouted else loss
