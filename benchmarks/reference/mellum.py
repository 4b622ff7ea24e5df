"""The Mellum block (JetBrains Mellum 2) in plain ``jax.numpy``: forward,
next-token loss and, through ``jax.grad``, gradients; float32
throughout, ``default_matmul_precision("highest")``, no kernels, no
routing tables, no sorting, NO EXCHANGE: the uncut layers over all
tokens.  It implements what the configuration's keys and its ``assumed``
list fix (``benchmarks/configs/mellum2-12b-a2.5b.json``), for one
sequence ``x [T, hidden]``; no bias anywhere, RMS norms with a learned
scale:

* a layer, pre-normed: ``a = x + Attn_kind(N1(x))``, ``y = a +
  MoE(N2(a))``; the kind by the layer's published index (every fourth
  ``full``, the others ``sliding``), the layers as run being the
  ``n_layer`` that END at the stage's last one;
* ``Attn``: ``q = W_q h -> [T, H, d]``, ``k = W_k h``, ``v = W_v h ->
  [T, Hkv, d]``; q and k rotated over the whole ``d``, halves rotated,
  positions from 0; causal softmax attention, query head ``i`` on K/V
  head ``i // (H / Hkv)``, scale ``d^-0.5``; ``W_o``.  No norm on q or
  k, no output gate.  Sliding layers: ``inv_freq_j = theta^(-2j/d)``,
  position ``t`` sees keys ``t - window + 1 .. t``.  Full layers, YaRN:
  ``low = floor(d ln(L / (beta_fast 2 pi)) / (2 ln theta))``, ``high =
  ceil(d ln(L / (beta_slow 2 pi)) / (2 ln theta))``, clipped to ``[0,
  d/2 - 1]``; ``ramp_j = clip((j - low) / (high - low), 0, 1)``;
  ``inv_freq'_j = (1 - ramp_j) inv_freq_j + ramp_j inv_freq_j /
  factor``; cos and sin BOTH times ``attention_factor``; all keys up to
  ``t`` seen;
* ``MoE``: ``s = softmax(W_r h)`` over ALL experts, ``S`` the ``k``
  largest, ``w_e = s_e / sum_{j in S} s_j``, ``sum_{e in S} w_e
  W_down,e (silu(W_gate,e h) * W_up,e h)``: every expert applied to ALL
  tokens under the mask ``[e in S]``.  No scale, no bias, no shared
  expert, no auxiliary loss;
* final RMS norm, untied head, mean next-token cross entropy over the
  whole vocabulary.

Departures, each on purpose, so that four sequences of 8,192 fit beside
the benchmark's training state: every layer runs the batch ONE SEQUENCE
AT A TIME, the query side of attention a block of positions at a time
against all keys, the experts a chunk of tokens at a time and ONE EXPERT
OF EVERY BLOCK at a time (``expert_blocks`` contiguous blocks of the
experts, as many as there are chips along the mesh axis the program's
preset lays the experts over: a step's experts are then one of every
chip's own, and the partitioner leaves the float32 matrices where they
lie), the head a chunk of tokens at a time; every layer, sequence, block
and chunk is under ``jax.checkpoint`` when gradients are taken —
recomputing changes no arithmetic.

It takes the program's parameter tree (``embed``, ``head``,
``final_norm``, ``h<i>`` with its ``attn`` and ``mlp`` parts; every
width is read from the tree's shapes and ``n_head``) and nothing else
from the program; :func:`init_like` alone reads the program's mesh and
preset, to put the weights it makes where the program's lie.  ``arch``
defaults to the configuration file's own keys.
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe import (  # noqa: F401 — the protocol
    _attend,
    _in_blocks,
    _rms,
    expand_layers,
    grad_error,
    score_gap,
)

#: |program loss - reference loss| / reference loss on one batch, each
#: with its OWN top-8 choices.  The program multiplies in bf16 with f32
#: accumulation, keeps a bf16 residual stream and sums the four chips'
#: parts of the routed layer in bf16.  On the chip at the cell's size
#: with the weights as the cell draws them (``EMBED_STD`` 2.0; my chip
#: runs, PR 51): 6.8e-7, 8.4e-7, 8.7e-7 in the cell's own runs, 2.2e-6
#: and 4.3e-6 in the controls' (call 7, two seeds).  The limit is that of
#: the accepted routed cells (Kanana-2's): it leaves the largest reading
#: 70 times of room.  What it can NOT see, as in the cells before it: at
#: initial weights attention is all but uniform and every logit small,
#: so all nine controls read 4e-7 .. 5.3e-5 (call 7: the scatter's sum
#: left out 5.3e-5 and 1.2e-5, the full layers rotated with the sliding
#: table 3.3e-5 and 3.4e-6, every float32 lowered 5.8e-7 and 2.1e-6); it
#: guards against a layer, the head or the labels gone wrong.  The
#: gradients and the routing limit decide the rest.
LOSS_RTOL = 3e-4
#: ||g_program - g_reference|| / ||g_reference|| over the whole tree,
#: both at the reference's routing (``mellum_paired.py``, which also
#: refuses a routing that is not the reference's up to near ties: the
#: error then reads exactly 1), on the stage's last two layers (sliding,
#: full), with the reduce-scatter's sum in bfloat16, which is the choice
#: these readings defend.  BOTH readings are of one run of
#: ``controls/mellum.py --seeds 2`` on the four chips with the weights as
#: the cell draws them (``EMBED_STD`` 2.0; my chip run, PR 51, call 7;
#: kept in ``controls/mellum.readings.jsonl``): the program **0.0045613
#: and 0.0045612** (the check's weights are ``PRNGKey(1)`` whatever the
#: seed; 0.00454 in the cell's own traced run); every float32 the
#: configuration states lowered to bfloat16, the nearest precision
#: below, **0.008990 and 0.008854**, arithmetic alone (its routing reads
#: 0.151% and 0.104% misrouted).  The two stand a factor 1.94 apart and
#: the limit at their geometric mean: 1.40 times the program's largest,
#: 1.38 under that control's smallest.  The other controls, arithmetic
#: alone, and the share of tokens they misroute, through which they
#: read 1: the full layers rotated with the sliding
#: table 0.091 (7.4-7.6%), ``attention_factor`` left out 0.079 (5.5-5.8%),
#: a window of 2,048 0.041 (7.7-7.9%), a sigmoid router 0.093 (4.8-4.9%),
#: weights not renormalised 0.113 (10%), the scatter's sum left out 0.168
#: (15%); a bfloat16 router 0.004612, the program's own arithmetic: the
#: routing limit alone sees it.  Bfloat16 head logits ALONE read as the
#: program does (0.00456157 against 0.00456133): neither limit sees them
#: (``controls/mellum.py`` ``UNSEEN``).  At its OWN routing the program
#: reads 0.0101 and 0.0099 (the flips), which is why the check pairs.
GRAD_RTOL = 0.0064

_HERE = os.path.dirname(os.path.abspath(__file__))


def _arch_of_file() -> Dict[str, Any]:
    with open(os.path.join(os.path.dirname(_HERE), "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        conf = json.load(f)
    rope = conf["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    kinds = conf["published"]["layer_types"]
    return {"window": conf["sliding_window"],
            "rope_theta": sliding["rope_theta"],
            "yarn": {k: full[k] for k in (
                "rope_theta", "factor", "original_max_position_embeddings",
                "beta_fast", "beta_slow", "attention_factor")},
            "top_k": conf["num_experts_per_tok"],
            "global_every": kinds.index("full_attention") + 1,
            "layer_stop": conf["as_run"]["layers"][-1] + 1}


ARCH = _arch_of_file()

#: the embedding's initial std.  No muP factor here and pre-norm only,
#: as Kanana-2 (``reference/deepseek_v3.py`` ``EMBED_STD``): where a
#: token's own part of the residual stream is small beside the
#: sub-layers' outputs (attention's is the running mean of v, COMMON to
#: neighbouring positions), every router sees much the same vector and
#: a few experts take most tokens.  Counted (this sandbox's CPU, the
#: stage's widths at 2 x 2,048 tokens, two seeds; an even router gives
#: 25%): the share of a layer's pairs that arrive on the FULLEST chip,
#: layer by layer, at Kanana's 0.5: 26, 25-27, 30-34, 30-36% (the
#: largest expert 4-5 times the mean in the last two layers); at 2.0:
#: 25.2-26.2% in all four (1.2); at 4.0 the same.  On the chip (my chip
#: runs, PR 51): at 0.5 25, 25, 26, 28-30%; at 2.0 24.6-25.4% in all
#: four (call 7).
#: WHY 2.0.  The cell stands for a deployment's traffic, and a deployed
#: router is a trained one, which its training holds near even: a
#: load-balancing term is part of every published recipe for such a
#: layer (the auxiliary loss of arXiv:2101.03961, section 2.2; the bias
#: update that stands in for it, arXiv:2408.15664, which measures the
#: fullest expert's load over the mean).  An expert at 4-5 times the
#: mean is a collapsed router, the state those terms exist to prevent,
#: and it is what random weights give at 0.5, not what this
#: configuration's users run; 1.2 is inside what they do.  Kanana's 0.5
#: was chosen on the same ground, after Trinity's rate swung with the
#: seed's routing (PERF.md, PRs 28 and 33); here it does not reach.
#: What first showed it: at 0.5 a step's time followed the batch's draw
#: and ``step_ms_p90`` spread 0.6% over three seeds, more than a new cell
#: is admitted under.  What the draw gives up, said plainly: the cell
#: does not measure UNEVEN routing (the buffers are still the worst
#: case's and nothing is dropped, but the chips' loads differ by a
#: percent): that is a traffic of its own (``ROADMAP.md`` R18); and the
#: deeper routers see little beside the token's own embedding.
EMBED_STD = 2.0

#: the program's logical axes by a leaf's name (``models/mellum.py``),
#: for :func:`init_like` to place what it makes
_AXES = {"embed": ("vocab", "embed"), "head": ("vocab", "embed"),
         "scale": ("embed",), "router": ("embed", None),
         "wq": ("embed", "heads"), "wk": ("embed", "kv"),
         "wv": ("embed", "kv"), "wo": ("heads", "embed"),
         "experts_gate": ("expert", "embed", "mlp"),
         "experts_up": ("expert", "embed", "mlp"),
         "experts_down": ("expert", "mlp", "embed")}


def _expert_blocks() -> int:
    """Chips along the mesh axis the program's preset lays the experts
    over (1 with no mesh)."""
    from ray_tpu.parallel.mesh import get_global_mesh
    from ray_tpu.parallel.sharding import FSDP_EP_RULES

    mesh = get_global_mesh()
    return 1 if mesh is None else mesh.shape[FSDP_EP_RULES.rules["expert"]]


def _placed(leaf, names):
    """``leaf`` constrained to where the program's preset puts a
    parameter of these logical axes on the global mesh (as it is, with
    no mesh, or where the axes do not divide it)."""
    from jax.sharding import NamedSharding

    from ray_tpu.parallel.mesh import get_global_mesh
    from ray_tpu.parallel.sharding import FSDP_EP_RULES, spec_axes

    mesh = get_global_mesh()
    if mesh is None or mesh.size == 1 or len(names) != leaf.ndim:
        return leaf
    spec = FSDP_EP_RULES.spec(*names)
    for dim, axis in zip(leaf.shape, spec):
        if axis is not None and dim % math.prod(
                mesh.shape[a] for a in spec_axes([axis])):
            return leaf
    return jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh, spec))


def init_like(shapes: Any, key: jax.Array) -> Any:
    """Random weights for a tree of shapes: N(0, 0.02) for every matrix,
    the head, the router and the stacked experts, N(0, ``EMBED_STD``) for
    the embedding, ones for the norm scales; a leaf at a time (an
    expert stack of all layers at once would be 6 GB), each PLACED on
    the global mesh as the program's preset places it (the harness's
    gradient check makes its weights through this with no sharding of
    its own, and 15.5 GB of weights and gradients fit only over the four
    chips)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for n, (path, leaf) in enumerate(flat):
        names = [str(getattr(p, "key", p)) for p in path]
        name = names[-2] if names[-1] == "kernel" else names[-1]
        if name == "scale":
            made = jnp.ones(leaf.shape, leaf.dtype)
        else:
            std = EMBED_STD if name == "embed" else 0.02
            made = std * jax.random.normal(
                jax.random.fold_in(key, n), leaf.shape, leaf.dtype)
        out.append(_placed(made, _AXES.get(name, ())))
    return jax.tree_util.tree_unflatten(treedef, out)


def yarn_inv_freq(dim: int, yarn: Dict[str, float]):
    """``(inv_freq [dim / 2], low, high)`` of the full layers' table."""
    base, length = yarn["rope_theta"], yarn["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(length / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair_of(yarn["beta_slow"])), dim // 2 - 1)
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    inv = 1.0 / base ** (2.0 * j / dim)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * inv + ramp * inv / yarn["factor"], low, high


def _rotation(dim: int, kind: str, arch):
    """``(inv_freq [dim / 2], factor on cos and sin)`` of a layer kind."""
    if kind == "full":
        return (yarn_inv_freq(dim, arch["yarn"])[0],
                arch["yarn"]["attention_factor"])
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    return 1.0 / arch["rope_theta"] ** (2.0 * j / dim), 1.0


def _rotate(x, rotation, start=0):
    """``[B, T, H, D]`` at positions ``start .. start + T - 1``: pairs
    ``(x[i], x[i + D/2])`` rotated, cos and sin times the factor."""
    inv, factor = rotation
    dim, seq = x.shape[-1], x.shape[1]
    ang = (start + jnp.arange(seq)).astype(jnp.float32)[:, None] * inv[None]
    cos = factor * jnp.cos(ang)[None, :, None]
    sin = factor * jnp.sin(ang)[None, :, None]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def route(h, w_router, top_k: int):
    """``h [T, E]`` -> (experts chosen ``[T, k]``, scores ``[T, N]``)."""
    s = jax.nn.softmax(h @ w_router, axis=-1)
    return jax.lax.top_k(s, top_k)[1], s


def expert_weights(h, p, arch, chosen=None):
    """``w [T, N]``: ``s_e / sum_{j in S} s_j`` for each expert ``e`` of
    the token's chosen set ``S``, 0 elsewhere (a token's weights sum to
    1); and the router's own choice ``[T, k]`` with its scores."""
    own, s = route(h, p["router"], arch["top_k"])
    chosen = own if chosen is None else chosen
    picked = jnp.take_along_axis(s, chosen, axis=1)
    w = picked / picked.sum(-1, keepdims=True)
    return jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        chosen, s.shape[1], dtype=jnp.float32)), (own, s)


def experts_under_mask(h, w, p, blocks: int = 1):
    """``sum_e w[t, e] expert_e(h[t])``: every expert applied to every
    token ``h [T, E]``, ONE EXPERT OF EVERY BLOCK a step (``blocks``
    contiguous blocks of the experts: ``[tokens, experts, width]`` at
    once is 1.9 GB a chunk of 256 tokens three times over, and a product
    over all experts at once would gather the matrices to every chip)."""
    n = p["experts_gate"].shape[0]
    per = n // blocks

    def cut(a):   # [N, ...] -> [blocks, per, ...]
        return a.reshape(blocks, per, *a.shape[1:])

    gate, up, down = (cut(p[k]) for k in (
        "experts_gate", "experts_up", "experts_down"))
    by_block = w.reshape(w.shape[0], blocks, per)

    def one(acc, j):
        def take(a, axis=1):
            return jax.lax.dynamic_index_in_dim(a, j, axis, keepdims=False)
        mid = jax.nn.silu(jnp.einsum("te,gem->gtm", h, take(gate))) \
            * jnp.einsum("te,gem->gtm", h, take(up))
        y = jnp.einsum("gtm,gme->gte", mid, take(down))
        return acc + jnp.einsum("tg,gte->te", take(by_block, 2), y), None

    out, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                          jnp.arange(per))
    return out


def _kernel(p, name):
    return p[name]["kernel"]


def _block(x, layer, kind, n_head, eps, arch, chosen, sizes):
    """One layer over ``x [B, T, E]``; and the router's own choice with
    its scores."""
    b, t, e = x.shape
    p = layer["attn"]
    d = _kernel(p, "wq").shape[1] // n_head
    kv = _kernel(p, "wk").shape[1] // d
    rotation = _rotation(d, kind, arch)
    h = _rms(x, p["attn_norm"]["scale"], eps)
    k = _rotate((h @ _kernel(p, "wk")).reshape(b, t, kv, d), rotation)
    v = (h @ _kernel(p, "wv")).reshape(b, t, kv, d)

    def attend(start, hq):
        """The query side of the sub-layer for one block of positions."""
        q = _rotate((hq @ _kernel(p, "wq")).reshape(b, -1, n_head, d),
                    rotation, start)
        a = _attend(q, k, v, start,
                    arch["window"] if kind == "sliding" else None)
        return a.reshape(b, -1, n_head * d) @ _kernel(p, "wo")

    x = x + _in_blocks(attend, sizes["query_block"], h)

    p = layer["mlp"]
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    w, own = expert_weights(h.reshape(b * t, e), p["moe"], arch, chosen)
    out = _in_blocks(
        lambda _, hc, wc: experts_under_mask(
            hc.reshape(-1, e), wc.reshape(-1, wc.shape[-1]), p["moe"],
            sizes["expert_blocks"]).reshape(hc.shape),
        sizes["token_chunk"], h, w.reshape(b, t, -1))
    return x + out, own


def layer_kinds(n_layer: int, arch) -> List[str]:
    """The ``n_layer`` layers that END at the stage's last one."""
    stop = arch["layer_stop"]
    return ["full" if (j + 1) % arch["global_every"] == 0 else "sliding"
            for j in range(stop - n_layer, stop)]


def hidden(params: Dict[str, Any], tokens: jax.Array, *, n_layer: int,
           n_head: int, ln_eps: float, arch: Optional[Dict] = None,
           choices: Optional[List[jax.Array]] = None,
           query_block: int = 64, token_chunk: int = 256,
           expert_blocks: Optional[int] = None, with_scores: bool = False):
    """(final normed hidden states ``[B, T, E]``, the float32 tree, the
    experts each layer's router chose ``[B*T, k]``; with ``with_scores``
    each of those a pair with the scores ``[B*T, N]``).  ``choices``:
    use THESE experts in place of the router's own top-k."""
    arch = dict(ARCH, **(arch or {}))
    sizes = {"query_block": query_block, "token_chunk": token_chunk,
             "expert_blocks": expert_blocks or _expert_blocks()}
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    batch, seq = tokens.shape

    def layer(x, p, given, kind):
        """One layer over the batch, ONE SEQUENCE AT A TIME."""
        def one(args):
            xi, mine = args
            out, own = _block(xi[None], p, kind, n_head, ln_eps, arch, mine,
                              sizes)
            return out[0], own
        return jax.lax.map(jax.checkpoint(one), (x, given))

    x = params["embed"][tokens]
    chose = []
    for i, kind in enumerate(layer_kinds(n_layer, arch)):
        given = None if choices is None \
            else choices[i].reshape(batch, seq, -1)
        x, own = jax.checkpoint(layer, static_argnums=(3,))(
            x, params[f"h{i}"], given, kind)
        chose.append(tuple(a.reshape(batch * seq, -1) for a in own))
    x = _rms(x, params["final_norm"]["scale"], ln_eps)
    return x, params, chose if with_scores else [own for own, _ in chose]


def forward(params, tokens, **kw):
    """``([B, T, V]`` float32 logits, the routers' choices)."""
    with jax.default_matmul_precision("highest"):
        x, params, chose = hidden(params, tokens, **kw)
        return x @ params["head"].T, chose


def flip_gaps(params, tokens, theirs: List[jax.Array], **kw):
    """Per layer ``(differ [B*T], gap [B*T])``: whether the reference's
    chosen set is another than ``theirs`` (another routing of the same
    tokens), and :func:`score_gap` of its own scores."""
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
    a chunk of positions at a time: ``[T, V]`` of one sequence at once is
    3.2 GB."""
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
