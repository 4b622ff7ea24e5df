"""What the harness's gradient check differentiates as the Mellum
program's loss (``entry.loss_fn`` of ``configs/mellum2-12b-a2.5b.json``):
the program's own ``loss_fn``, every token routed to the experts the
REFERENCE chose, and 0 where the program's own routing is not the
reference's up to near ties.  Why, and what the two parts of the
comparison are: ``afmoe_paired.py``.  The scores here are a SOFTMAX over
64 experts (of order 1/64, where a sigmoid's are of order 1/2), so the
gap that counts as a near tie is this file's own, and so is the share
of misrouted tokens it allows.

The first loss of every run compares the program with its OWN choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import mellum as reference

#: a token is misrouted if the program chose an expert whose reference
#: score (a softmax over 64, the chosen ones of order 0.03-0.08) is more
#: than this below one it left out.  The program's routers see a
#: bfloat16 stream: a logit of unit spread moves by some 0.002 and a
#: chosen score by 1e-4, so gaps up to there are near ties that both
#: sides decide rightly.  On the chip at the check's size with the
#: weights as the cell draws them (my chip run, PR 51, call 7, two
#: seeds: ``controls/mellum.readings.jsonl``; the share of 65,536 tokens
#: of two layers misrouted at a gap of 1e-4 / 2e-4 / 3e-4 / 5e-4): the
#: program 0.75-0.79% / 0.17-0.22% / **0.038-0.046%** / 0-0.005%; with
#: a bfloat16 router 1.6-1.7% / 0.68-0.77% / **0.229-0.284%** /
#: 0.011-0.027%.  At 3e-4 the two stand a factor 5 apart (3.1 at 2e-4),
#: and the counts are still of 25-30 tokens against 150-186
ROUTING_GAP = 3e-4
#: most tokens, of all of all layers, that may be misrouted: the
#: geometric mean of the program's largest, 0.0458%, and the bfloat16
#: router's smallest, 0.229%, at that gap: 2.2 times the one (65 tokens
#: where it counted 25 and 30) and 2.3 under the other.  Every float32
#: lowered reads 0.104-0.151% (the gradient limit is what has to see
#: it); every other control that touches what a router sees 4.8-15%
MISROUTED_MAX = 0.001


def reference_routing(cfg, params, tokens, arch=None):
    """Per layer ``(choice [B*T, k], scores [B*T, N])`` of the reference
    (which runs a sequence at a time itself).  ``arch``: its constants
    where they are not the configuration file's."""
    with jax.default_matmul_precision("highest"):
        return reference.hidden(
            jax.lax.stop_gradient(params), tokens, n_layer=cfg.num_layers,
            n_head=cfg.num_heads, ln_eps=cfg.rms_eps, arch=arch,
            with_scores=True)[2]


def misrouted_share(routed, own, gap: float = ROUTING_GAP) -> jax.Array:
    """Of all tokens of all layers, the share whose ``own`` choice takes
    an expert that the reference scores more than ``gap`` below one it
    leaves out."""
    return jnp.mean(jnp.stack([
        reference.score_gap(scores, choice, theirs) > gap
        for (choice, scores), theirs in zip(routed, own)]))


def judged(value, misrouted: jax.Array):
    """``value`` (the loss, or its gradient: the one is the other's,
    ``where`` being linear) where the program's routing is the
    reference's up to near ties, else 0: the gradient error then reads
    exactly 1."""
    return jax.tree.map(
        lambda a: jnp.where(misrouted <= MISROUTED_MAX, a, 0.0), value)


def paired_loss(model, params, tokens, routed, **kw):
    """``(the program's loss at the routing ``routed``
    (:func:`reference_routing`), NOT yet judged; the share of tokens its
    own routing misroutes; its own choices a layer)``."""
    from ray_tpu.models.mellum import loss_fn

    loss, own = loss_fn(model, params, tokens, with_choices=True,
                        choices=[choice for choice, _ in routed], **kw)
    return loss, misrouted_share(routed, own), own


def program_loss(model, params, tokens, arch=None, with_misrouted=False,
                 **kw):
    """``with_misrouted``: also the share of misrouted tokens."""
    loss, misrouted, _ = paired_loss(
        model, params, tokens,
        reference_routing(model.config, params, tokens, arch), **kw)
    loss = judged(loss, misrouted)
    return (loss, misrouted) if with_misrouted else loss
