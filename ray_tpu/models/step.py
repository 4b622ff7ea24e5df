"""What a train step is made of, said once for every model.

A compiled step has no host spans inside it: its spans are the names
the program puts on its ops (``jax.named_scope``, flax's module names),
which XLA keeps as each instruction's ``op_name`` and the profiler
writes beside each device op.  :data:`PARTS` is the ONE list of the
top-level pieces a step is split by; the models open them through
:func:`scope`, and :func:`make_train_step`, the step the models' entry
points share, says the list in the timeline (``model:step.scopes``),
which is where a reader of a trace takes it from.

An op belongs to the OUTERMOST component of its name that is a part,
whole or before a dot (``attn.sliding``, ``attn.full`` and ``attn.mla``
are ``attn``; ``mla.kv_up`` is a child of whatever part it stands in),
so it is in at most one.  A scope is a Python context at trace time and
a string in the instruction's metadata: the jaxpr, the compiled code
and the step's memory are what they are without it.

**The part ``attn``, one level down.**  :data:`ATTN_PIECES` is the ONE
list of what an attention half is made of in every model file, opened
as ``scope("attn.<piece>")`` and said beside the parts in the plan span
(``attn_pieces``).  An op's piece is the INNERMOST component of its
name that is ``attn.<piece>`` with ``<piece>`` on the list: a kind
(:data:`ATTN_KINDS`, around the kernels' call) or a plain name
(``mla.kv_up``, a flax module's) is no piece and hides none outside it.

**What a block recomputes, said once too.**  A block that recomputes a
part in the backward pass wraps it in :func:`remat`, and that recomputes
everything BUT what the part stamped with a name of :data:`KEPT`
(:func:`keep`): the discrete decisions of a routed call, the router's
choices ``[T, k]`` and the row plan made from them.  A decision is not
recomputed: it has no backward, so making it again buys nothing (an
``argsort`` and a ``top_k`` a layer-call), and a recomputed ``top_k``
whose near tie falls the other way would take the backward for a
routing the loss never ran.  Beside the decisions, :data:`KEPT_SUMS`:
a hyper-connection's projection, ``n (n + 2) + 1`` float32 a token that
cost a pass over all of the token's lanes (``models/hyper.py``).  A part
that stamps nothing keeps nothing.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.core import telemetry
from ray_tpu.ops.fused import HEAD_GRADIENT

#: the parts of a step, in the order a step meets them
PARTS = ("embed", "hc.coef", "hc.mix", "attn", "mlp", "moe.route",
         "moe.exchange", "moe.plan", "moe.dispatch", "moe.experts",
         "moe.combine", "ssm.in_proj", "ssm.conv", "ssm.scan",
         "ssm.gate_norm", "ssm.out_proj", "mtp", "head", "exit",
         "optimizer")

#: the pieces of the part ``attn``.  ``norm``: the residual stream's
#: side, its read (a sequence taken out of the batch), the pre- and
#: post-norms, the per-head and latent norms, the residual add;
#: ``proj``: every matrix product with its bias and the split or reshape
#: of its result; ``pos``: the rotation, tables and application, and the
#: join of rotated and unrotated halves; ``gate``: the output gate's
#: sigmoid and product; ``layout``: what ``ops/flash_attention.py`` does
#: around its kernel calls that is no kernel (transposes, packed
#: reshapes, ``delta``, the ``shard_map``); ``kernel``: the kernel
#: calls, or the plain ``jnp`` form that stands for them off a TPU.
#: ``ops/`` imports no model: it says the last two as literals
#: (``tests/test_step_scopes.py`` holds them to this list)
ATTN_PIECES = ("norm", "proj", "pos", "gate", "layout", "kernel")
#: what a call of the kernels is, around the call: no piece
ATTN_KINDS = ("sliding", "full", "mla")

#: what a recomputed part keeps of its forward: a routed call's decisions
KEPT = ("choices", "plan")
#: and the few sums a token that a whole pass over its lanes made
KEPT_SUMS = ("projection",)
_KEEP_THESE = jax.checkpoint_policies.save_only_these_names(
    *KEPT, *KEPT_SUMS)


def part_of(component: str) -> Optional[str]:
    """The part a name component is: itself, or what stands before a dot
    of it (``attn.sliding`` -> ``attn``); ``None`` for any other name."""
    for part in PARTS:
        if component == part or component.startswith(part + "."):
            return part
    return None


def scope(name: str):
    """``with scope("attn"):`` around the trace of a part's work, or of
    a named piece of it (``attn.proj``): ``jax.named_scope``, for the
    names of :data:`PARTS` alone; under ``attn.`` for a piece or a kind
    alone, so that a typo is no unpieced time."""
    if part_of(name) is None:
        raise ValueError(f"{name!r} is no part of a step: {PARTS}")
    if name.startswith("attn.") \
            and name[len("attn."):] not in ATTN_PIECES + ATTN_KINDS:
        raise ValueError(f"{name!r} is no piece of attn {ATTN_PIECES} "
                         f"and no kind of it {ATTN_KINDS}")
    return jax.named_scope(name)


def names_its_parts(module: nn.Module):
    """``with names_its_parts(m): m(x)`` around the call of a flax module
    whose work is MORE than one part (a routed MLP: the shared expert is
    ``mlp``, the routed experts are the five ``moe.*``): flax would put
    the module's own name around all of it, and that name (``mlp``) is a
    part.  A module whose class sets ``names_its_parts`` is called with
    flax's naming off and turns it on again for its children
    (:func:`named_children`); any other module is called as ever."""
    if getattr(module, "names_its_parts", False):
        return nn.override_named_call(False)
    return contextlib.nullcontext()


def named_children():
    """The other half of :func:`names_its_parts`, around the body of
    such a module's ``__call__``."""
    return nn.override_named_call(True)


def remat(module):
    """``module`` (a flax class), recomputed in the backward pass but for
    what its forward stamped with a name of :data:`KEPT`: the ONE way a
    block wraps a part for recompute."""
    return nn.remat(module, policy=_KEEP_THESE)


def keep(name: str, tree):
    """``tree``, every array of it stamped ``name`` (of :data:`KEPT` or
    :data:`KEPT_SUMS`):
    under :func:`remat` the backward pass reads the forward's values and
    what made them is not run again; anywhere else, ``tree`` as it is.
    An array is kept FLAT and takes its shape again behind the stamp: a
    TPU lays a last axis out over 128 lanes, so a kept ``[T, 4]`` holds
    32 times its bytes from the forward to the backward (Mellum's step
    compiled for a described v5e: a peak of 14.91 GiB with the tables
    kept in their shape, 14.16 flat; ``tests/test_chip_compile.py``
    holds it under 14.5)."""
    if name not in KEPT + KEPT_SUMS:
        raise ValueError(
            f"{name!r} is not kept under remat: {KEPT + KEPT_SUMS}")
    return jax.tree.map(lambda a: checkpoint_name(
        a.reshape(-1), name).reshape(a.shape), tree)


def make_train_step(loss: Callable[[Any, jax.Array], jax.Array], tx, *,
                    remat: str = "",
                    plan: Optional[Callable[[Any], Any]] = None):
    """The donated, jitted ``(params, opt_state, tokens) -> (params,
    opt_state, loss)`` every model's ``make_train_step`` returns:
    ``loss(params, tokens)`` and its gradients, then one ``tx`` (optax)
    update under the part ``optimizer``.  Params and optimizer state are
    donated so XLA updates them in place (saves an HBM copy of the full
    state per step).  ``plan(params)``: a context around the trace of
    loss and gradients (GPT-2's ``fsdp_plan``).  A trace leaves the span
    ``model:step.scopes`` (``parts``: :data:`PARTS` comma-joined,
    ``attn_pieces``: :data:`ATTN_PIECES` likewise, ``remat``, ``head``:
    how the chunked head comes by its gradient, ``ops/fused.py``'s
    ``HEAD_GRADIENT``); nothing runs with the step."""
    import optax

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens):
        with telemetry.span("model", "step.scopes", parts=",".join(PARTS),
                            attn_pieces=",".join(ATTN_PIECES),
                            remat=remat, head=HEAD_GRADIENT):
            with plan(params) if plan else contextlib.nullcontext():
                value, grads = jax.value_and_grad(
                    lambda p: loss(p, tokens))(params)
            with scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
        return params, opt_state, value

    return train_step
