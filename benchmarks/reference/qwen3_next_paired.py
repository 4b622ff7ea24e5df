"""What the harness's gradient check differentiates as the Qwen3-Next
program's loss (``entry.loss_fn`` of ``configs/qwen3-next-80b-a3b.json``):
the program's own ``loss_fn``, every token routed to the experts the
REFERENCE chose, and 0 where the program's own routing is not the
reference's up to near ties.  Why, and what the two parts of the
comparison are: ``afmoe_paired.py``.  The scores here are a SOFTMAX over
512 experts (of order 1/512, where a sigmoid's are of order 1/2), so the
gap that counts as a near tie is this file's own, and so is the share
of misrouted tokens it allows.

A third part is this model's own, :func:`scan_error`, as
``nemotron_h_paired.py``'s and for its reason: the program's scan op
against the reference's step-by-step recurrence IN FLOAT32, at the
cell's shapes, on the first mixer's own inputs for the first sequence.
What a rounded carry between chunks, or a dropped one, does to a
bfloat16 step is noise among noise at random weights; with float32
operands the op agrees with the recurrence to rounding, and a carry
rounded to bfloat16, or dropped, does not.  Past ``SCAN_RTOL`` the loss
returned is 0 as well.

A fourth part, :func:`route_error`: the program's router (scores,
choices and WEIGHTS, ``models/afmoe.py`` ``route`` under this model's
configuration) against the reference's weights, in float32, on the first
layer's own input.  Why: a sigmoid for the softmax chooses the SAME
experts (both are monotone in the logits), so the routing limit cannot
see it, and at initial weights the routed experts move the loss and the
whole-tree gradient in their fourth digit; the weights themselves differ
by a tenth.  Past ``ROUTE_RTOL`` the loss returned is 0 as well.

The first loss of every run compares the program with its OWN choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import qwen3_next as reference

#: a token is misrouted if the program chose an expert whose reference
#: score (a softmax over 512, the chosen ones of order 0.004-0.01) is
#: more than this below one it left out.  The program's routers see a
#: bfloat16 stream: gaps up to some 1e-4 are near ties that both sides
#: decide rightly.  On the chip at the check's size (my chip run, PR 58,
#: call 3: the share of the 24,576 tokens of three layers misrouted at a
#: gap of 2e-5 / 4e-5 / **8e-5** / 1.6e-4): the program 2.48% / 1.13% /
#: **0.151%** / 0; on bfloat16 parameters 1.95% / 0.71% / 0.077% / 0;
#: every float32 lowered (a bfloat16 router) 4.45% / 2.41% / **0.696%** /
#: 0.016%; no carry and a bfloat16 carry 0.18%; q and k not normalised
#: 29%; ``beta`` = 1 29%; no decay 99%.  At 8e-5 the program and the
#: bfloat16 router stand a factor 4.6 apart (2.1 at 4e-5) and the counts
#: are still of 37 tokens against 171
ROUTING_GAP = 8e-5
#: most tokens, of all of all layers, that may be misrouted: the
#: geometric mean of the program's 0.151% and the bfloat16 router's
#: 0.696% at that gap is 0.32%: twice the one, 2.3 under the other.  It
#: is the WRONG-LAYER guard (29-99%); the bfloat16 router is the
#: router's own probe's to refuse, below
MISROUTED_MAX = 0.003
#: ||program scan - recurrence|| / ||recurrence||, float32 operands under
#: ``default_matmul_precision("highest")``, one sequence at the cell's
#: shapes (4,096 positions, 64 chunks, 32 heads of 128 x 128).  On the
#: chip (my chip runs, PR 58, calls 2 and 3): the op 3.56e-7 and 3.53e-7;
#: its carry rounded to bfloat16 at every chunk 2.83e-5 and 2.86e-5;
#: every stated float32 lowered (``g``, ``beta`` and the carry rounded)
#: 1.65e-3; no correction 9.0e-3; the carry dropped 1.88e-2; ``beta`` = 1
#: 1.0; no decay 12.8.  The limit is the geometric mean of the first
#: two, a factor 8.5 from the op and 9.4 from the bfloat16 carry; the
#: probe's weights are ``PRNGKey(1)``'s in every run.  What it does NOT
#: see: the timed step runs the op on bfloat16 operands; the float32
#: carry of that call is the same ``lax.scan`` of the same body
#: (``ops/gated_delta.py`` ``chunk_step``: its carry is float32 whatever
#: the operands), covered through this float32-operand call
SCAN_RTOL = 3e-6
#: ||program weights - reference weights|| / ||reference weights|| over
#: the held experts of the first layer, float32 against float32 (the
#: program's router multiplies at ``Precision.HIGHEST``), at the
#: reference's choices.  On the chip (my chip run, PR 58, call 3): the
#: program 0.0 (bit for bit); a bfloat16 router 0.0045; a sigmoid for the
#: softmax 0.305
ROUTE_RTOL = 1e-4


def misrouted_share(routed, own, gap: float = ROUTING_GAP) -> jax.Array:
    """Of all tokens of all layers, the share whose ``own`` choice takes
    an expert that the reference scores more than ``gap`` below one it
    leaves out."""
    return jnp.mean(jnp.stack([
        reference.score_gap(scores, choice, theirs) > gap
        for (choice, scores), theirs in zip(routed, own)]))


def scan_error(model, params, tokens, arch=None) -> jax.Array:
    """The program's scan (``ray_tpu.models.qwen3_next.gated_delta``, as
    its mixers call it) against ``reference.recurrence`` on what the
    FIRST mixer is given for the first sequence (the embeddings through
    its norm, projections, convolution, l2 norms and gates), everything
    float32."""
    from ray_tpu.models import qwen3_next as program

    f32 = jnp.float32
    cfg = model.config
    arch = dict(reference.ARCH, **(arch or {}))
    p = jax.tree.map(lambda a: jax.lax.stop_gradient(a).astype(f32),
                     params["h0"]["mixer"])
    with jax.default_matmul_precision("highest"):
        x = jax.lax.stop_gradient(params["embed"]).astype(f32)[tokens[0]]
        _, q, k, v, g, beta = reference.scan_inputs(x, p, cfg.rms_eps, arch)
        want = reference.recurrence(q, k, v, g, beta, min(128, q.shape[0]))
        got = program.gated_delta(q[None], k[None], v[None], g[None],
                                  beta[None], chunk=cfg.chunk)[0]
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(
        want.ravel())


def route_error(model, params, tokens, arch=None) -> jax.Array:
    """The program's routing weights (``afmoe.route`` under the model's
    configuration: its ``score_func``, ``route_scale`` and
    ``router_dtype``, at the experts the reference chose) against
    ``reference.held_weights``, on the first
    layer's router and the first sequence's normed embeddings, both from
    float32 inputs; the program's ``[T, k]`` weights laid over the held
    experts as the reference lays its own."""
    from ray_tpu.models import afmoe

    f32 = jnp.float32
    cfg = model.config
    arch = dict(reference.ARCH, **(arch or {}))
    p = jax.tree.map(lambda a: jax.lax.stop_gradient(a).astype(f32),
                     params["h0"]["mlp"])
    with jax.default_matmul_precision("highest"):
        x = jax.lax.stop_gradient(params["embed"]).astype(f32)[tokens[0]]
        h = reference._norm(x, p["mlp_norm"]["weight"], cfg.rms_eps)
        want, (own, _) = reference.held_weights(h, p["moe"], arch)
        # at the reference's choices: a near tie that falls the other
        # way is the routing limit's to judge, not this one's
        idx, weights, _ = afmoe.route(cfg, h, p["moe"]["router"], own)
    ids = cfg.experts_held[0] + jnp.arange(want.shape[1])
    got = jnp.einsum("tk,tke->te", weights.astype(f32),
                     (idx[:, :, None] == ids).astype(f32))
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(
        want.ravel())


def reference_routing(cfg, params, tokens, arch=None):
    """Per layer ``(choice [B*T, k], scores [B*T, N])`` of the reference
    (which runs a sequence at a time itself).  ``arch``: its constants
    where they are not the configuration file's."""
    with jax.default_matmul_precision("highest"):
        return reference.hidden(
            jax.lax.stop_gradient(params), tokens, n_layer=cfg.num_layers,
            n_head=cfg.num_heads, ln_eps=cfg.rms_eps, arch=arch,
            with_scores=True)[2]


#: the gaps a builder's readings are taken at (``controls/qwen3_next.py``)
GAPS = (2e-5, 4e-5, 8e-5, 1.6e-4, 3.2e-4, 6.4e-4)


def sound(probes) -> jax.Array:
    """Whether the three probes of a paired loss are within their
    limits."""
    return (probes["misrouted"] <= MISROUTED_MAX) \
        & (probes["scan_error"] <= SCAN_RTOL) \
        & (probes["route_error"] <= ROUTE_RTOL)


def program_loss(model, params, tokens, arch=None, with_misrouted=False,
                 with_probes=False, **kw):
    """The program's loss at the reference's routing, 0 where a probe is
    past its limit.  ``with_misrouted``: also the share of misrouted
    tokens.  ``with_probes`` (a builder's readings): the loss NOT yet
    judged, and every probe's reading (``misrouted``, ``scan_error``,
    ``route_error``, and ``misrouted_at`` the share at each of
    ``GAPS``)."""
    from ray_tpu.models.qwen3_next import loss_fn

    routed = reference_routing(model.config, params, tokens, arch)
    loss, own = loss_fn(model, params, tokens, with_choices=True,
                        choices=[choice for choice, _ in routed], **kw)
    probes = {"misrouted": misrouted_share(routed, own),
              "scan_error": scan_error(model, params, tokens, arch),
              "route_error": route_error(model, params, tokens, arch)}
    if with_probes:
        probes["misrouted_at"] = jnp.stack(
            [misrouted_share(routed, own, gap) for gap in GAPS])
        return loss, probes
    loss = jnp.where(sound(probes), loss, 0.0)
    return (loss, probes["misrouted"]) if with_misrouted else loss
