"""The DeepSeek-V3 block as Kanana-2-30B-A3B configures it, in plain
``jax.numpy``: forward, next-token loss and, through ``jax.grad``,
gradients; float32 throughout, ``default_matmul_precision("highest")``,
no kernels, no routing tables, no sorting.  It implements what the
configuration's keys and its ``assumed`` list fix (``benchmarks/configs/
kanana-2-30b-a3b.json``), for one sequence ``x [T, hidden]``:

* attention (MLA, no query latent): ``q = W_q h -> [T, H, nope + rope]``;
  ``a = W_kva h -> [T, rank + rope]``, ``c = RMSNorm(a[:, :rank])`` (its
  own scale), ``k_rope = a[:, rank:]``, ONE head; ``[k_nope | v] = W_kvb
  c -> [T, H, nope + dv]``.  RoPE (theta from the file) on ``q``'s last
  ``rope`` elements and on ``k_rope``, PAIRS INTERLEAVED: elements ``2i,
  2i+1`` rotated by ``t * theta^(-2i/rope)``.  The key is written out
  plainly, ``k = [k_nope | k_rope broadcast over the heads]``; score
  ``q . k * (nope + rope)^-0.5``, causal softmax, ``sum p v -> [T, H *
  dv] -> W_o``;
* pre-norm only: ``x + attn(norm(x))``, ``x + mlp(norm(x))``, a final
  norm before the untied head;
* dense layer: ``W_down(silu(W_gate h) * W_up h)``;
* expert layer: ``s = sigmoid(W_r h)`` over ALL published experts, ``S``
  the ``k`` largest (one group: no group limit; the selection bias is
  zero at initialisation and left out), ``w_e = routed_scaling_factor *
  s_e / sum_{j in S} s_j``, result ``shared(h) + sum_{e in S and held
  here} w_e expert_e(h)``: each held expert applied to ALL tokens under
  the mask ``[e in S]``; ``shared`` is one SwiGLU of the shared experts'
  joint width;
* next-token cross entropy over the vocabulary slice, no auxiliary term.

Departures, each on purpose, so that two sequences of 16,384 fit beside
the benchmark's training state: every layer runs the batch ONE SEQUENCE
AT A TIME (``lax.map``), attention a GROUP OF HEADS at a time (a quarter of them:
heads are independent up to the sum inside ``W_o``, so a group takes its
columns of ``W_q`` and ``W_kvb`` and its rows of ``W_o`` and the groups'
results are added) and within a group the query side a block of
positions at a time against all keys, the MLPs and the head a chunk of
tokens at a time; every layer, sequence, part of a layer, group, block
and chunk is under ``jax.checkpoint`` when gradients are taken —
recomputing changes no arithmetic.  Sized on the compiler for a
described v5e (PERF.md, PR 33): the harness's gradient check, both
gradients in one program, takes 6.51 GiB of temporaries beside 7.68 GiB
of training state and its own 1.31 GiB of parameters; with the batch
outermost, or all heads at once, it does not fit.

It takes the program's parameter tree (``embed``, ``head``,
``final_norm``, ``dense<d>``, ``h<i>``, each layer with its ``attn`` and
``mlp`` parts; every width is read from the tree's shapes and
``n_head``), the experts held (their count from the tree, the first from
``arch``), and nothing else from the program.  ``arch`` defaults to the
configuration file's own keys.  The pieces that are the same plain
arithmetic for any routed model (RMS norm, SwiGLU, the experts under a
mask, the blocking helper, the gradient error) are
``benchmarks/reference/afmoe.py``'s.
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe import (  # noqa: F401 — the protocol
    _in_blocks,
    _rms,
    _swiglu,
    expand_layers,
    experts_under_mask,
    grad_error,
    held_weights,
    score_gap,
)

#: |program loss - reference loss| / reference loss on one batch, each
#: with its OWN top-6 choices.  The program multiplies in bf16 with f32
#: accumulation and keeps a bf16 residual stream.  On the chip at the
#: cell's size (my chip runs, PR 33), seven seeds: 6.7e-6, 7.2e-6,
#: 7.7e-6, 8.3e-6, 1.7e-5, 2.9e-5, 5.7e-5.  The scatter is the flips: 4-9%
#: of a layer's tokens choose another top-6 in bf16 than in f32, and each
#: moves its own term of the loss either way.  Trinity-Mini's 1e-4 would
#: leave the largest reading less than twice of room, and the driver
#: draws fresh seeds: the limit is five times the largest reading.  What
#: it can NOT see: at initial weights attention is all but uniform and
#: the routed experts move the loss little, so every control reads
#: 4e-6 .. 7.5e-5 as the sound program does (half the shared experts
#: left out: 5.2e-5 and 2.4e-4); it guards against a layer, the head or
#: the labels gone wrong.  The gradients and the routing limit decide
#: the rest.
LOSS_RTOL = 3e-4
#: ||g_program - g_reference|| / ||g_reference|| over the whole tree,
#: both at the reference's routing (``deepseek_v3_paired.py``, which
#: also refuses a routing that is not the reference's up to near ties:
#: the error then reads exactly 1).  On the chip (my chip runs, PR 33;
#: PERF.md section 6): the program 0.00951-0.00959 (0.0247-0.0258 at its own
#: routing: flips of 4-9% of a layer's tokens); the CPU rehearsal at
#: width 64 0.0053-0.0054.  The parameters rounded to bfloat16, the
#: nearest precision below and the only control that routes as the sound
#: program does (0.76-0.81% misrouted), read 0.0203-0.0208: the limit
#: lies between, a factor 1.45 from each.  Every other control reads 1
#: through the routing limit.
GRAD_RTOL = 0.014

_HERE = os.path.dirname(os.path.abspath(__file__))


def _arch_of_file() -> Dict[str, Any]:
    with open(os.path.join(os.path.dirname(_HERE), "configs",
                           "kanana-2-30b-a3b.json")) as f:
        conf = json.load(f)
    return {"rope_theta": conf["rope_theta"],
            "route_scale": conf["routed_scaling_factor"],
            "top_k": conf["num_experts_per_tok"],
            "first_held": conf["as_run"]["experts_held"][0]}


ARCH = _arch_of_file()

#: the embedding's initial std.  This model has no muP factor, so at
#: 0.02 a token's own part of the residual stream has rms 0.02 beside
#: attention's output, the running mean of v over the positions before
#: (rms 0.6 / sqrt(t) at random weights, COMMON to neighbouring
#: positions): the routers then see much the same vector for every
#: token, and the share of choices that land on an eighth of the experts
#: reads 10.8-16.8% a layer with the largest expert at 1.6-3.4 times the
#: mean; at 0.1 11.8-14.3% (1.2-1.7), at 0.5 12.1-13.0% (1.1-1.3) (my
#: chip run, PR 33, three seeds, 16,384 tokens; an even router gives
#: 12.5%).  The cell's rate must not swing with the seed's routing, as
#: Trinity-Mini's did (PERF.md, PR 28)
EMBED_STD = 0.5


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


def rope_interleaved(x, theta, start=0):
    """``[B, T, H, D]`` at positions ``start .. start + T - 1``: the
    pair ``(x[2i], x[2i+1])`` rotated by ``t * theta^(-2i/D)``."""
    dim, seq = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = (start + jnp.arange(seq)).astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _attend(q, k, v, start):
    """One block of queries ``q [B, bq, H, D]`` at positions ``start ..``
    against all keys ``k [B, T, H, D]``, ``v [B, T, H, Dv]``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    keep = (start + jnp.arange(q.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _kernel(p, name):
    return p[name]["kernel"]


def _attention_part(x, p, n_head, eps, arch, sizes):
    """``x + attention(norm(x))`` for ``x [B, T, E]``."""
    b, t, e = x.shape
    rank = p["kv_norm"]["scale"].shape[0]
    rope = _kernel(p, "wkv_a").shape[1] - rank
    nope = _kernel(p, "wq").shape[1] // n_head - rope
    theta = arch["rope_theta"]
    h = _rms(x, p["attn_norm"]["scale"], eps)
    a = h @ _kernel(p, "wkv_a")
    latent = _rms(a[..., :rank], p["kv_norm"]["scale"], eps)
    k_rope = rope_interleaved(a[..., rank:].reshape(b, t, 1, rope), theta)
    # a GROUP of heads at a time (heads are independent up to the sum
    # inside W_o): a group's columns of W_q and W_kvb, its rows of W_o
    groups = math.gcd(n_head, sizes["head_groups"])
    hg = n_head // groups

    def cols(w):   # [in, heads * d] -> [groups, in, hg * d]
        return jnp.moveaxis(w.reshape(w.shape[0], groups, -1), 1, 0)

    def group(acc, w):
        w_q, w_kvb, w_o = w
        kv = (latent @ w_kvb).reshape(b, t, hg, -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, hg, rope))],
            axis=-1)
        v = kv[..., nope:]

        def attend(start, hq):
            """The query side for one block of positions."""
            q = (hq @ w_q).reshape(b, -1, hg, nope + rope)
            q = jnp.concatenate(
                [q[..., :nope],
                 rope_interleaved(q[..., nope:], theta, start)], axis=-1)
            out = _attend(q, k, v, start)
            return out.reshape(b, out.shape[1], -1) @ w_o

        return acc + _in_blocks(attend, sizes["query_block"], h), None

    w_o = _kernel(p, "wo")
    attn, _ = jax.lax.scan(
        jax.checkpoint(group), jnp.zeros_like(x),
        (cols(_kernel(p, "wq")), cols(_kernel(p, "wkv_b")),
         w_o.reshape(groups, -1, w_o.shape[1])))
    return x + attn


def _mlp_part(x, p, eps, arch, chosen, sizes):
    """``x + mlp(norm(x))``, and the router's own choice with its
    scores (``None`` in a dense layer)."""
    b, t, e = x.shape
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
    return x + out, own


def _block(x, layer, n_head, eps, arch, chosen, sizes):
    """One layer; each of its two parts under its own checkpoint."""
    x = jax.checkpoint(
        lambda x, p: _attention_part(x, p, n_head, eps, arch, sizes)
    )(x, layer["attn"])
    return jax.checkpoint(
        lambda x, p: _mlp_part(x, p, eps, arch, chosen, sizes)
    )(x, layer["mlp"])


def hidden(params: Dict[str, Any], tokens: jax.Array, *, n_layer: int,
           n_head: int, ln_eps: float, arch: Optional[Dict] = None,
           choices: Optional[List[jax.Array]] = None,
           query_block: int = 64, token_chunk: int = 256,
           head_groups: int = 8, with_scores: bool = False):
    """(final normed hidden states ``[B, T, E]``, the float32 tree, the
    experts each expert layer's router chose ``[B*T, k]``; with
    ``with_scores`` each of those a pair with the scores ``[B*T, N]``).
    ``choices``: use THESE experts in place of the router's own top-k
    (the program's, to tell a flipped near tie from a wrong layer)."""
    arch = dict(ARCH, **(arch or {}))
    sizes = {"query_block": query_block, "token_chunk": token_chunk,
             "head_groups": head_groups}
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    n_dense = sum(1 for k in params if k.startswith("dense"))
    names = [f"dense{i}" for i in range(n_dense)] + [
        f"h{i}" for i in range(n_layer)]
    batch, seq = tokens.shape

    def layer(x, p, given):
        """One layer over the batch, ONE SEQUENCE AT A TIME."""
        def one(args):
            xi, mine = args
            out, own = _block(xi[None], p, n_head, ln_eps, arch, mine, sizes)
            return out[0], own
        return jax.lax.map(jax.checkpoint(one), (x, given))

    x = params["embed"][tokens]
    chose = []
    for n, name in enumerate(names):
        given = None
        if choices is not None and n >= n_dense:
            given = choices[n - n_dense].reshape(batch, seq, -1)
        x, own = jax.checkpoint(layer)(x, params[name], given)
        if own is not None:
            chose.append(tuple(a.reshape(batch * seq, -1) for a in own))
    x = _rms(x, params["final_norm"]["scale"], ln_eps)
    return x, params, chose if with_scores else [own for own, _ in chose]


def forward(params, tokens, **kw):
    """``([B, T, V]`` float32 logits, the routers' choices)."""
    with jax.default_matmul_precision("highest"):
        x, params, chose = hidden(params, tokens, **kw)
        return x @ params["head"].T, chose


def flip_gaps(params, tokens, theirs: List[jax.Array], **kw):
    """Per expert layer ``(differ [B*T], gap [B*T])``: whether the
    reference's chosen set is another than ``theirs`` (another routing
    of the same tokens), and :func:`score_gap` of its own scores."""
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
    a chunk of positions at a time."""
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
