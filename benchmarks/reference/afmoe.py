"""The AFMoE block (arcee-ai Trinity family) in plain ``jax.numpy``:
forward, next-token loss and, through ``jax.grad``, gradients; float32
throughout, ``default_matmul_precision("highest")``, no kernels, no
routing tables, no sorting.  It implements what the configuration's keys
and its ``assumed`` list fix (``benchmarks/configs/trinity-mini.json``):

* attention: ``q = W_q h`` (``n_head`` heads), ``k = W_k h``, ``v = W_v
  h`` (fewer heads; query head ``i`` attends K/V head ``i // group``),
  q and k RMS-normalised per head, RoPE (rotate-half, theta 10000) on q
  and k in ``sliding_attention`` layers ONLY, scores scaled by
  ``head_dim ** -0.5``, causal; a sliding layer's position ``t`` sees
  ``t - window + 1 .. t``; the output times ``sigmoid(W_g h)``, then
  ``W_o``;
* every sub-layer as ``x + post_norm(f(pre_norm(x)))``, RMS norms;
* dense layer: ``W_down(silu(W_gate h) * W_up h)``;
* expert layer: ``s = sigmoid(W_r h)`` over ALL published experts, ``S``
  the ``k`` largest, ``w_e = route_scale * s_e / sum_{j in S} s_j``,
  result ``shared(h) + sum_{e in S and held here} w_e expert_e(h)``:
  each held expert applied to ALL tokens under the mask ``[e in S]``;
  what absent experts would add is left out;
* the embedding times ``sqrt(hidden)`` (muP), final RMS norm, untied
  head, cross entropy over the vocabulary slice.

Departures, each on purpose: the query side of attention runs a block
of positions at a time (against all keys) and the MLPs a chunk of tokens
at a time, so that 8,192 positions and 16 experts fit beside the
benchmark's training state; for the same reason every block, chunk and
layer is under ``jax.checkpoint`` when gradients are taken —
recomputing changes no arithmetic.  The source's auxiliary
load-balance term and its selection bias (zero at initialisation) are
left out, as in the program.

It takes the program's parameter tree (``embed``, ``head``,
``final_norm``, ``dense<d>``, ``h<i>``, each layer with its ``attn`` and
``mlp`` parts), the experts held (their count
from the tree, the first from ``arch``), and nothing else from the
program.  ``arch`` defaults to the configuration file's own keys.
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

#: |program loss - reference loss| / reference loss on one batch, each
#: with its OWN top-8 choices.  The program multiplies in bf16 with f32
#: accumulation and keeps a bf16 residual stream.  On the chip at the
#: cell's size, nine seeds (PERF.md, PR 28): 1.1e-6 .. 2.3e-5 (5.0e-6
#: given the program's choices where its own read 5.3e-6); a window one
#: tile short 3.4e-4.  What it can NOT see: ``route_scale`` left out
#: (3.1e-5), dropped rows (5.9e-5) and lower precision (2.8e-5): at
#: initial weights the routed experts move the loss little; the
#: gradients see those.
LOSS_RTOL = 1e-4
#: ||g_program - g_reference|| / ||g_reference|| over the whole tree,
#: both at the reference's routing (``afmoe_paired.py``, which also
#: refuses a routing that is not the reference's up to near ties: the
#: error then reads exactly 1).  On the chip, seven seeds (PERF.md, PR 28):
#: the program 0.0091-0.0102 (0.0194-0.0207 at its own routing: the
#: flips of 4-7% of a layer's tokens); the CPU rehearsal at width 64
#: 0.0168-0.0183.  The controls on the chip: buffers for half the rows
#: an even router lands, so rows are dropped, 0.064-0.080 (and 4.7-5.9%
#: of the tokens misrouted, so under the routing limit it reads 1); a
#: window one tile short and ``route_scale`` left out route 83% and 14%
#: of the tokens otherwise and read 1; every float32 the configuration
#: states lowered to bfloat16 misroutes 6.3-6.5% and reads 1 (its
#: arithmetic alone 0.0160-0.0169).  bf16 PARAMETERS alone read
#: 0.0160-0.0169 with 0.9-1.0% misrouted and pass: the
#: program's matmuls round the weights to bfloat16 anyway, and what
#: float32 parameters buy is the optimizer's sum, which no step-0 check
#: sees; a limit under 0.016 would fail the rehearsal's sound program.
GRAD_RTOL = 0.03

_HERE = os.path.dirname(os.path.abspath(__file__))


def _arch_of_file() -> Dict[str, Any]:
    with open(os.path.join(os.path.dirname(_HERE), "configs",
                           "trinity-mini.json")) as f:
        conf = json.load(f)
    run = conf["as_run"]
    return {"window": conf["sliding_window"],
            "rope_theta": conf["rope_theta"],
            "route_scale": conf["route_scale"],
            "top_k": conf["num_experts_per_tok"],
            "global_every": conf["global_attn_every_n_layers"],
            "mup": conf["mup_enabled"],
            "first_held": run["experts_held"][0],
            "expert_layer_start": run["expert_layer_start"]}


ARCH = _arch_of_file()


#: the embedding's initial std.  Times ``sqrt(hidden)`` (muP) a token's
#: own part of the residual stream then has rms 3.6 beside the unit-rms
#: sub-layer outputs.  At 0.02 (rms 0.9) a third of what a router sees is
#: the attention output's component COMMON to all positions (at random
#: weights attention averages), every token prefers the same experts, and
#: the share of choices landing on an eighth of the experts swings 8-17%
#: layer by layer and seed by seed: the cell's rate then spread 0.41-0.47%
#: over six seeds, against the 0.5% a new cell is admitted under
#: (PERF.md, PR 28)
EMBED_STD = 0.08


def init_like(shapes: Any, key: jax.Array) -> Any:
    """Random weights for a tree of shapes: N(0, 0.02) for every matrix,
    the head, the router and the stacked experts, N(0, ``EMBED_STD``) for
    the embedding, ones for the norm scales.  Leaves alike in name and
    shape are drawn as ONE stacked array."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: Dict[Any, list] = {}
    for i, (path, leaf) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        groups.setdefault((name, leaf.shape, leaf.dtype), []).append(i)
    out: list = [None] * len(flat)
    for n, ((name, shape, dtype), where) in enumerate(groups.items()):
        if name == "scale":
            block = jnp.ones((len(where), *shape), dtype)
        else:
            std = EMBED_STD if name == "embed" else 0.02
            block = std * jax.random.normal(
                jax.random.fold_in(key, n), (len(where), *shape), dtype)
        for j, i in enumerate(where):
            out[i] = block[j]
    return jax.tree_util.tree_unflatten(treedef, out)


def expand_layers(tree: Dict[str, Any], n_layer: int) -> Dict[str, Any]:
    """A tree with ONE expert layer ``h0`` standing for every expert
    layer: ``h0`` .. ``h<n_layer-1>`` all alike; the dense layers, the
    embedding, the head and the final norm as they are."""
    out = {k: v for k, v in tree.items() if k != "h0"}
    out.update({f"h{i}": tree["h0"] for i in range(n_layer)})
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta, start=0):
    """``[B, T, H, D]`` at positions ``start .. start + T - 1``,
    rotate-half."""
    dim, seq = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = (start + jnp.arange(seq)).astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attend(q, k, v, start, window: Optional[int]):
    """One block of queries ``q [B, bq, H, D]`` at positions ``start ..``
    against all keys ``k v [B, T, Hkv, D]`` -> ``[B, bq, H, D]``."""
    b, bq, h, d = q.shape
    kv, t = k.shape[2], k.shape[1]
    q = q.reshape(b, bq, kv, h // kv, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * d ** -0.5
    q_pos = start + jnp.arange(bq)
    k_pos = jnp.arange(t)
    keep = q_pos[:, None] >= k_pos[None]
    if window is not None:
        keep &= q_pos[:, None] - k_pos[None] < window
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(b, bq, h, d)


def _in_blocks(fn, block: int, *arrays):
    """``fn(start, *blocks)`` over blocks of ``block`` positions of axis 1
    of every array, results joined along axis 1.  Each block under
    ``jax.checkpoint``: a gradient keeps one block's intermediates."""
    t = arrays[0].shape[1]
    block = min(block, t)
    assert t % block == 0, (t, block)

    def cut(a):
        return jnp.moveaxis(
            a.reshape(a.shape[0], t // block, block, *a.shape[2:]), 1, 0)

    out = jax.lax.map(
        jax.checkpoint(lambda args: fn(args[0], *args[1:])),
        (jnp.arange(0, t, block), *[cut(a) for a in arrays]))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], t, *out.shape[3:])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(h, w_router, top_k: int, route_scale: float):
    """``h [T, E]`` -> (experts chosen ``[T, k]``, scores ``[T, N]``)."""
    s = jax.nn.sigmoid(h @ w_router)
    return jax.lax.top_k(s, top_k)[1], s


def held_weights(h, p, arch, chosen=None):
    """``w [T, held]``: ``route_scale * s_e / sum_{j in S} s_j`` for each
    held expert ``e`` in the token's chosen set ``S``, 0 elsewhere; and
    the router's own choice ``[T, k]`` with its scores ``[T, N]``."""
    held = p["experts_gate"].shape[0]
    own, s = route(h, p["router"], arch["top_k"], arch["route_scale"])
    chosen = own if chosen is None else chosen
    picked = jnp.take_along_axis(s, chosen, axis=1)            # [T, k]
    w = arch["route_scale"] * picked / picked.sum(-1, keepdims=True)
    ids = arch["first_held"] + jnp.arange(held)
    return jnp.einsum("tk,tke->te", w,
                      (chosen[:, :, None] == ids[None, None]
                       ).astype(jnp.float32)), (own, s)


def experts_under_mask(h, w_held, p):
    """``sum_e w_e[t] expert_e(h[t])``: every held expert applied to
    every token ``h [T, E]``, weighted by ``w_held [T, held]``."""
    gate = jnp.einsum("te,fem->ftm", h, p["experts_gate"])
    up = jnp.einsum("te,fem->ftm", h, p["experts_up"])
    y = jnp.einsum("ftm,fme->fte", jax.nn.silu(gate) * up,
                   p["experts_down"])
    return jnp.einsum("tf,fte->te", w_held, y)


def _routed(h, p, arch, chosen, chunk: int):
    """The routed part for ``h [T, E]`` in chunks of tokens; and the
    router's own choice."""
    w_held, (own, _) = held_weights(h, p, arch, chosen)
    out = _in_blocks(
        lambda _, hc, wc: experts_under_mask(hc[0], wc[0], p)[None],
        chunk, h[None], w_held[None])
    return out[0], own


def _kernel(p, name):
    return p[name]["kernel"]


def _block(x, layer, kind, n_head, eps, arch, chosen, sizes):
    b, t, e = x.shape
    p = layer["attn"]
    d = p["q_norm"]["scale"].shape[0]
    kv = _kernel(p, "wk").shape[1] // d
    sliding = kind == "sliding"
    theta = arch["rope_theta"]
    h = _rms(x, p["attn_norm"]["scale"], eps)
    k = _rms((h @ _kernel(p, "wk")).reshape(b, t, kv, d),
             p["k_norm"]["scale"], eps)
    v = (h @ _kernel(p, "wv")).reshape(b, t, kv, d)
    if sliding:
        k = _rope(k, theta)

    def attend(start, hq):
        """The query side of the sub-layer for one block of positions."""
        q = _rms((hq @ _kernel(p, "wq")).reshape(b, -1, n_head, d),
                 p["q_norm"]["scale"], eps)
        if sliding:
            q = _rope(q, theta, start)
        a = _attend(q, k, v, start, arch["window"] if sliding else None)
        a = a.reshape(b, -1, n_head * d) * jax.nn.sigmoid(
            hq @ _kernel(p, "wg"))
        return a @ _kernel(p, "wo")

    a = _in_blocks(attend, sizes["query_block"], h)
    x = x + _rms(a, p["attn_post_norm"]["scale"], eps)

    p = layer["mlp"]
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    own = None
    if "moe" in p:
        w_held, own = held_weights(h.reshape(b * t, e), p["moe"], arch,
                                   chosen)

        def mlp(_, hc, wc):
            shared = _swiglu(hc, _kernel(p, "shared_gate"),
                             _kernel(p, "shared_up"),
                             _kernel(p, "shared_down"))
            routed = experts_under_mask(hc.reshape(-1, e),
                                        wc.reshape(-1, wc.shape[-1]),
                                        p["moe"])
            return shared + routed.reshape(hc.shape)

        out = _in_blocks(mlp, sizes["token_chunk"], h,
                         w_held.reshape(b, t, -1))
    else:
        out = _in_blocks(
            lambda _, hc: _swiglu(hc, _kernel(p, "w_gate"),
                                  _kernel(p, "w_up"), _kernel(p, "w_down")),
            sizes["token_chunk"], h)
    return x + _rms(out, p["mlp_post_norm"]["scale"], eps), own


def layer_kinds(n_dense: int, n_layer: int, arch) -> List[str]:
    index = list(range(n_dense)) + [arch["expert_layer_start"] + i
                                    for i in range(n_layer)]
    return ["full" if (j + 1) % arch["global_every"] == 0 else "sliding"
            for j in index]


def hidden(params: Dict[str, Any], tokens: jax.Array, *, n_layer: int,
           n_head: int, ln_eps: float, arch: Optional[Dict] = None,
           choices: Optional[List[jax.Array]] = None,
           query_block: int = 64, token_chunk: int = 256,
           with_scores: bool = False):
    """(final normed hidden states ``[B, T, E]``, the float32 tree, the
    experts each expert layer's router chose ``[B*T, k]``; with
    ``with_scores`` each of those a pair with the scores ``[B*T, N]``).
    ``choices``: use THESE experts in place of the router's own top-k
    (the program's, to tell a flipped near tie from a wrong layer)."""
    arch = dict(ARCH, **(arch or {}))
    sizes = {"query_block": query_block, "token_chunk": token_chunk}
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    n_dense = sum(1 for k in params if k.startswith("dense"))
    x = params["embed"][tokens]
    if arch["mup"]:
        x = x * math.sqrt(x.shape[-1])
    names = [f"dense{i}" for i in range(n_dense)] + [
        f"h{i}" for i in range(n_layer)]
    chose = []
    for n, (name, kind) in enumerate(zip(
            names, layer_kinds(n_dense, n_layer, arch))):
        given = None
        if choices is not None and n >= n_dense:
            given = choices[n - n_dense]
        x, own = jax.checkpoint(
            lambda x, p, kind=kind, given=given: _block(
                x, p, kind, n_head, ln_eps, arch, given, sizes)
        )(x, params[name])
        if own is not None:
            chose.append(own if with_scores else own[0])
    return _rms(x, params["final_norm"]["scale"], ln_eps), params, chose


def forward(params, tokens, **kw):
    """``([B, T, V]`` float32 logits, the routers' choices)."""
    with jax.default_matmul_precision("highest"):
        x, params, chose = hidden(params, tokens, **kw)
        return x @ params["head"].T, chose


def logits(params, tokens, **kw) -> jax.Array:
    return forward(params, tokens, **kw)[0]


def score_gap(scores, own, theirs) -> jax.Array:
    """``[T]``: by how much ``scores [T, N]`` prefer the chosen set ``own
    [T, k]`` to ``theirs [T, k]``: the largest score in ``own`` and not
    in ``theirs``, less the smallest in ``theirs`` and not in ``own``; 0
    where the sets are equal."""
    n = scores.shape[1]
    mine = jax.nn.one_hot(own, n).sum(1) > 0
    them = jax.nn.one_hot(theirs, n).sum(1) > 0
    best_missed = jnp.where(mine & ~them, scores, -jnp.inf).max(-1)
    worst_taken = jnp.where(them & ~mine, scores, jnp.inf).min(-1)
    return jnp.where((mine != them).any(-1), best_missed - worst_taken,
                     0.0)


def flip_gaps(params, tokens, theirs: List[jax.Array], **kw):
    """Per expert layer ``(differ [B*T], gap [B*T])``: whether the
    reference's chosen set is another than ``theirs`` (another routing
    of the same tokens), and :func:`score_gap` of its own scores.  A
    routing that differs only at near ties reads small everywhere."""
    with jax.default_matmul_precision("highest"):
        routed = hidden(params, tokens, with_scores=True, **kw)[2]
    out = []
    for (own, s), other in zip(routed, theirs):
        differ = (jnp.sort(own, -1) != jnp.sort(other, -1)).any(-1)
        out.append((differ, score_gap(s, own, other)))
    return out


def loss_sum(params, tokens, **kw) -> jax.Array:
    """Sum over the batch of next-token negative log likelihoods (labels
    are the tokens shifted left; the last position has none).  The head
    a chunk of positions at a time: ``[B, T, V]`` at once is 1.6 GB three
    times over when gradients are taken."""
    with jax.default_matmul_precision("highest"):
        x, params, _ = hidden(params, tokens, **kw)
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        last = jnp.arange(tokens.shape[1]) == tokens.shape[1] - 1

        def nll(start, xc, yc, skip):
            logp = jax.nn.log_softmax(xc @ params["head"].T, -1)
            picked = jnp.take_along_axis(logp, yc[..., None], -1)[..., 0]
            return jnp.where(skip, 0.0, -picked)

        return _in_blocks(nll, kw.get("token_chunk", 256), x, labels,
                          jnp.broadcast_to(last, tokens.shape)).sum()


def loss(params, tokens, **kw) -> jax.Array:
    """Mean next-token cross entropy, as the program's ``loss_fn``."""
    b, t = tokens.shape
    return loss_sum(params, tokens, **kw) / (b * (t - 1))


def grad_error(g_program, g_reference) -> jax.Array:
    """Relative L2 distance between two gradient trees (jittable)."""
    num = sum(jnp.sum(jnp.square(a.astype(jnp.float32) - b))
              for a, b in zip(jax.tree.leaves(g_program),
                              jax.tree.leaves(g_reference)))
    den = sum(jnp.sum(jnp.square(b)) for b in jax.tree.leaves(g_reference))
    return jnp.sqrt(num / den)
