"""What the harness's gradient check differentiates as the DeepSeek-V3
program's loss (``entry.loss_fn`` of ``configs/kanana-2-30b-a3b.json``):
the program's own ``loss_fn``, every token routed to the experts the
REFERENCE chose, and 0 where the program's own routing is not the
reference's up to near ties.  Why, and what the two parts of the
comparison are: ``afmoe_paired.py``, whose count of misrouted tokens
this is (one routed layer serves both models); the reference that does
the choosing differs, and the share of misrouted tokens it allows.

The first loss of every run compares the program with its OWN choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import deepseek_v3 as reference
from benchmarks.reference.afmoe_paired import (  # noqa: F401
    ROUTING_GAP,
    misrouted_share,
)

#: most tokens, of all of all expert layers, that may be misrouted (an
#: expert taken that the reference scores more than ``ROUTING_GAP``
#: below one left out).  On the chip at the cell's size (my chip runs,
#: PR 33): the program 1.24-1.25%; of the controls the nearest are the
#: latent's norm left out, 3.37-3.42%, the scores scaled by 128^-0.5,
#: 3.95-4.01%, and a bfloat16 router on bfloat16 parameters, 5.26-5.36%
#: (RoPE off the key or paired by halves, no routing scale: 11.6-11.8%;
#: half the shared experts: 45%).  Trinity-Mini's 0.03 would pass the
#: first of them with a tenth to spare
MISROUTED_MAX = 0.02


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
    from ray_tpu.models.deepseek_v3 import loss_fn

    routed = reference_routing(model.config, params, tokens, arch)
    loss, own = loss_fn(model, params, tokens, with_choices=True,
                        choices=[choice for choice, _ in routed], **kw)
    misrouted = misrouted_share(routed, own)
    loss = jnp.where(misrouted <= MISROUTED_MAX, loss, 0.0)
    return (loss, misrouted) if with_misrouted else loss
