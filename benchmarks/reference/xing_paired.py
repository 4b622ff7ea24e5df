"""What the harness's gradient check differentiates as the Xing4.0
program's loss (``entry.loss_fn`` of ``configs/xing4.0-29b-a4b.json``):
the program's own ``loss_fn``, every token routed to the experts the
REFERENCE chose, and 0 where the program's own routing is not the
reference's up to near ties.  Why, and what the two parts of the
comparison are: ``afmoe_paired.py``, whose count of misrouted tokens
this is (one routed layer serves both models); the reference that does
the choosing differs.  With a multi-token module its routed layer is
replayed and counted like any other, last.

The first loss of every run compares the program with its OWN choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import xing as reference
from benchmarks.reference.afmoe_paired import (  # noqa: F401
    ROUTING_GAP,
    misrouted_share,
)

#: most tokens, of all of all routed layers, that may be misrouted (an
#: expert taken that the reference scores more than ``ROUTING_GAP``
#: below one left out).  On the chip at the check's size (my chip runs,
#: PR 54): the program 0.81% (0.90% with the multi-token module's layer),
#: four Sinkhorn steps 0.85%, bfloat16 coefficients 0.95%; every float32
#: the configuration states lowered to bfloat16 2.92%, the projection on
#: unnormalised lanes 4.30%, no routing scale 5.16%, no query norm 8.00%,
#: plain RoPE 14.1%, the softmax scale without ``m^2`` 16.1%, ``H_post``
#: without its 2 70.1%.  Over three draws of the check's weights (the
#: review session's call, other tokens): the program 0.90%, 1.14%, 0.90%,
#: bfloat16 coefficients 0.93-1.00%, every float32 lowered 3.60%, 3.52%,
#: 3.42%.  Kanana's limit lies between, with a factor 1.75 above the
#: program's largest and 1.46 under the lowered precision's smallest
MISROUTED_MAX = 0.02


def reference_routing(cfg, params, tokens, arch=None):
    """Per routed layer ``(choice [B*T, k], scores [B*T, N])`` of the
    reference, a sequence at a time.  ``arch``: its constants where they
    are not the configuration file's."""
    fixed = jax.lax.stop_gradient(params)

    def one(row, _):
        with jax.default_matmul_precision("highest"):
            return reference.hidden(
                fixed, row, n_layer=cfg.num_layers, n_head=cfg.num_heads,
                ln_eps=cfg.rms_eps, arch=arch, with_scores=True)[2]

    return [(c.reshape(-1, c.shape[-1]), s.reshape(-1, s.shape[-1]))
            for c, s in reference.each_sequence(one, tokens)]


def program_loss(model, params, tokens, arch=None, with_misrouted=False,
                 **kw):
    from ray_tpu.models.deepseek_v3 import loss_fn

    routed = reference_routing(model.config, params, tokens, arch)
    loss, own = loss_fn(model, params, tokens, with_choices=True,
                        choices=[choice for choice, _ in routed], **kw)
    misrouted = misrouted_share(routed, own)
    loss = jnp.where(misrouted <= MISROUTED_MAX, loss, 0.0)
    return (loss, misrouted) if with_misrouted else loss
