"""The Qwen3-Next stack as Qwen3-Next-80B-A3B-Instruct configures it, in
plain ``jax.numpy``: forward, next-token loss and, through ``jax.grad``,
gradients; float32 throughout, ``default_matmul_precision("highest")``,
no kernels, NO CHUNKS, no routing tables, no sorting.  It implements what
the configuration's keys and its ``assumed`` list fix
(``benchmarks/configs/qwen3-next-80b-a3b.json``), for one sequence ``x
[T, hidden]``; every layer is ``x = x + mixer(N(x))``, ``x = x +
moe(N(x))``, ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``; layer ``i``
is full attention where ``(i + 1) % full_attention_interval == 0``, else
linear attention:

* ``L``, the Gated DeltaNet mixer.  ``[q | k | v], z = W_qkvz h``
  (widths ``2 H_k d + H_v d``, ``H_v d``), ``b, a = W_ba h`` (``H_v``
  each).  ``c_t = silu(sum_j w[j] * [q | k | v]_{t-3+j})``, four shifted
  products, zeros before the sequence, NO bias.  Per head ``q <- q /
  sqrt(sum q^2 + 1e-6) * d^-0.5``, ``k <- k / sqrt(sum k^2 + 1e-6)``;
  key head ``j`` serves value heads ``r j .. r j + r - 1``.  ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``.  THE
  RECURRENCE, STEP BY STEP (``lax.scan`` over time; segments of it under
  ``jax.checkpoint`` so that its backward fits): ``S <- exp(g_t) S``,
  ``d_t = beta_t (v_t - S^T k_t)``, ``S <- S + k_t d_t^T``, ``o_t = S^T
  q_t``.  ``y = w_n * (o / sqrt(mean(o^2) + eps)) * silu(z)`` over each
  head's width, the norm FIRST; ``W_out``;
* ``F``, attention: ``q, gate = W_q h`` (``heads x d`` each), ``k, v``
  (``kv x d``); ``q <- N_d(q)``, ``k <- N_d(k)`` per head (``1 + w``);
  the FIRST ``rotary`` elements of each head rotated (halves paired,
  ``inv_freq_j = theta^(-2j / rotary)``); causal softmax of ``q . k *
  d^-0.5``, query head ``n`` on K/V head ``n // (heads / kv)``, a block
  of queries at a time; the output times ``sigmoid(gate)``; ``W_o``;
* the expert MLP of every layer: ``p = softmax(W_r h)`` over ALL
  published experts, ``S`` the ``k`` largest, ``w_e = p_e / sum_{j in S}
  p_j``, result ``sigmoid(w_s . h) shared(h) + sum_{e in S and held
  here} w_e expert_e(h)``: each held expert applied to ALL tokens under
  the mask ``[e in S]``; every expert, the shared one too, ``W_down(
  silu(W_gate h) * W_up h)``;
* a final norm, the untied head, next-token cross entropy over the
  vocabulary slice, no auxiliary term.

Departures, each on purpose, so that two sequences of 4,096 fit beside
the benchmark's training state: every layer runs the batch ONE SEQUENCE
AT A TIME (``lax.map``), attention's query side a block of positions at
a time against all keys, the MLPs and the head a chunk of tokens at a
time; every layer, sequence, block, chunk and scan segment is under
``jax.checkpoint`` when gradients are taken: recomputing changes no
arithmetic.

It takes the program's parameter tree (``embed``, ``head``,
``final_norm``; layers ``h<i>`` with ``mixer`` or ``attn`` and ``mlp``;
the two joint projections in the program's column order, ``[q | k | v]``
then ``z``, and ``q`` then ``gate``: ``assumed``), the experts held
(their count from the tree, the first from ``arch``), and nothing else
from the program.  ``arch`` defaults to the configuration file's own
keys; the pattern of ``n_layer`` linear layers is the share's ``L^n F``
unless ``arch`` gives one.  The blocking helper, one block's attention,
the score gap and the gradient error are ``benchmarks/reference/
afmoe.py``'s: the same plain arithmetic for any model.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe import (  # noqa: F401 — the protocol
    _attend,
    _in_blocks,
    _rope,
    _swiglu,
    grad_error,
    score_gap,
)

#: |program loss - reference loss| / reference loss on one batch, each
#: with its OWN top-10 choices.  The program multiplies in bf16 with f32
#: accumulation and keeps a bf16 residual stream.  On the chip at the
#: cell's size (my chip runs, PR 58, calls 1-3, four seeds): 2.4e-6 ..
#: 9.4e-6; the scatter is the flips: 590-1,089 of a layer's 16,384 tokens
#: choose another top-10 in bf16 than in f32.  The limit is 6.4 times the
#: largest reading (the driver draws fresh seeds).  What it sees of the
#: controls (call 3): a norm's scale ``w`` for ``1 + w`` 0.040, no decay
#: 3.3e-4, the shared expert ungated 9.0e-5; the rest read 7e-7 .. 3.9e-5
#: (``beta`` = 1), as the sound program does: at initial weights a mixer
#: moves the loss little, and no precision moves it (every float32
#: lowered reads 6.6e-6).  It guards against a layer, the head or the
#: labels gone wrong; the gradients and the three probes decide the rest.
LOSS_RTOL = 6e-5
#: ||g_program - g_reference|| / ||g_reference|| over the whole tree,
#: both at the reference's routing (``qwen3_next_paired.py``, which also
#: refuses a routing that is not the reference's up to near ties, a scan
#: that is not the recurrence's in float32 and a router whose weights are
#: not the reference's: the error then reads exactly 1).  On the chip at
#: the check's size, ``L L F`` on two sequences of 4,096 (my chip run,
#: PR 58, call 3): the program 0.005019 (0.005496 at its own routing, and
#: 0.005486 on another seed's tokens: the check's weights are
#: ``PRNGKey(1)``'s whatever the seed, so the reading moves in its third
#: digit); the parameters rounded to bfloat16, the nearest precision
#: below and the only control that routes, scans and weighs as the sound
#: program does, 0.005360: the program's products round the weights to
#: bfloat16 anyway, so what float32 parameters change at step 0 is the
#: norms, the taps, the decays and the router, 6.8% of the reading.
#: Nearer still: the gated norm's order turned round (gate, then norm)
#: reads 0.005256 and leaves routing, scan and router as they are: at
#: initial weights ``silu(z)`` is nearly linear in a small ``z`` and the
#: two orders differ in the second order.  The limit is the geometric
#: mean of the sound reading and that control's, 2.3% from either and a
#: dozen times the reading's own scatter: thin, and the widest a step-0
#: check of this program can be.  Every float32 lowered reads 0.005365
#: raw and 1 through the router's probe (0.0045), the scan's (1.65e-3)
#: and the routing limit (0.70% misrouted); no carry 0.005332 raw and 1
#: through the scan's probe (0.0154); no correction 0.005133 raw and 1
#: through the scan's probe (0.0090); a sigmoid for the softmax 0.0158
#: raw and 1 through the router's probe (0.295); the rest read 0.036 ..
#: 1.0 raw and 1 through the routing limit (2.9% .. 100% misrouted):
#: PERF.md section 6, PR 58.  It was PLACED AFTER those readings: it
#: first stood at 0.012, then at 0.0052 (call 3b: every control caught,
#: the gated norm's order by 1.1%).
GRAD_RTOL = 0.00514

_HERE = os.path.dirname(os.path.abspath(__file__))


def _arch_of_file() -> Dict[str, Any]:
    with open(os.path.join(os.path.dirname(_HERE), "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        conf = json.load(f)
    return {"top_k": conf["num_experts_per_tok"],
            "first_held": conf["as_run"]["experts_held"][0],
            "head_dim": conf["head_dim"],
            "rotary_dim": int(conf["head_dim"]
                              * conf["partial_rotary_factor"]),
            "rope_theta": conf["rope_theta"],
            "key_heads": conf["linear_num_key_heads"],
            "value_heads": conf["linear_num_value_heads"],
            "interval": conf["full_attention_interval"],
            "pattern": None}


ARCH = _arch_of_file()

#: the embedding's initial std.  This model has no muP factor, and a
#: token's own part of the residual stream has to stand out beside what
#: the mixers add, which is COMMON to neighbouring positions: else every
#: token prefers the same experts and the share of choices that lands on
#: the held sixteenth swings seed by seed (``reference/nemotron_h.py``
#: found 2.0 for the same reason; the readings here: PERF.md, PR 58)
EMBED_STD = 2.0


def published_pattern(layers: int, interval: int) -> str:
    return "".join("F" if (i + 1) % interval == 0 else "L"
                   for i in range(layers))


def init_like(shapes: Any, key: jax.Array) -> Any:
    """Random weights for a tree of shapes, by the source's initialisers
    (``assumed`` in the configuration file): zeros for the zero-centred
    norm weights, ones for the gated norm's scale and ``dt_bias``;
    ``A_log`` the log of a uniform draw in (0, 16); N(0, 0.02) for every
    matrix, the convolution, the head, the router and the stacked
    experts; N(0, ``EMBED_STD``) for the embedding.  Leaves alike in name
    and shape are drawn as ONE stacked array."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: Dict[Any, list] = {}
    for i, (path, leaf) in enumerate(flat):
        names = [str(getattr(p, "key", p)) for p in path]
        name = names[-2] if names[-1] == "kernel" else names[-1]
        groups.setdefault((name, leaf.shape, leaf.dtype), []).append(i)
    out: list = [None] * len(flat)
    for n, ((name, shape, dtype), where) in enumerate(groups.items()):
        full, k = (len(where), *shape), jax.random.fold_in(key, n)
        if name == "weight":
            block = jnp.zeros(full, dtype)
        elif name in ("gate_norm", "dt_bias"):
            block = jnp.ones(full, dtype)
        elif name == "A_log":
            block = jnp.log(jax.random.uniform(
                k, full, jnp.float32, 1e-4, 16.0)).astype(dtype)
        else:
            std = EMBED_STD if name == "embed" else 0.02
            block = std * jax.random.normal(k, full, dtype)
        for j, i in enumerate(where):
            out[i] = block[j]
    return jax.tree_util.tree_unflatten(treedef, out)


def expand_layers(tree: Dict[str, Any], n_layer: int) -> Dict[str, Any]:
    """A depth-1 tree ``(h0, h1)``, ``L F``, standing for the share's
    ``L^n F``: ``h0`` .. ``h<n-1>`` linear layers all alike, ``h<n>`` the
    full-attention layer; the embedding, the head and the final norm as
    they are."""
    out = {k: v for k, v in tree.items() if k not in ("h0", "h1")}
    out.update({f"h{i}": tree["h0"] for i in range(n_layer)})
    out[f"h{n_layer}"] = tree["h1"]
    return out


def _kernel(p, name):
    return p[name]["kernel"]


def _norm(x, w, eps):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


# ---------------------------------------------------------------------------
# L: the Gated DeltaNet mixer
# ---------------------------------------------------------------------------

def causal_conv(u, w):
    """``silu(sum_j w[j] * u[t - (taps - 1) + j])`` for ``u [T, C]``, ``w
    [taps, C]``: shifted products, zeros before the sequence, no bias."""
    taps, t = w.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1])), u])
    return jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(taps)))


def l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta, segment: int = 128):
    """The gated delta rule step by step for one sequence: ``q k [T, H_k,
    d]``, ``v [T, H_v, d]``, ``g beta [T, H_v]`` -> ``o [T, H_v, d]``.
    ``segment`` steps under one ``jax.checkpoint``: a gradient keeps a
    state a segment, and a segment's states while it is differentiated."""
    t, heads, _ = v.shape
    rep = heads // k.shape[1]
    segment = min(segment, t)
    assert t % segment == 0, (t, segment)

    def step(S, inp):
        qt, kt, vt, gt, bt = inp
        qt, kt = jnp.repeat(qt, rep, 0), jnp.repeat(kt, rep, 0)   # [H, d]
        S = jnp.exp(gt)[:, None, None] * S
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    def run(S, inp):
        return jax.lax.scan(step, S, inp)

    def cut(a):
        return a.reshape(t // segment, segment, *a.shape[1:])

    first = jnp.zeros((heads, k.shape[2], v.shape[2]), jnp.float32)
    _, o = jax.lax.scan(jax.checkpoint(run), first,
                        tuple(map(cut, (q, k, v, g, beta))))
    return o.reshape(v.shape)


def scan_inputs(x, p, eps, arch):
    """Everything of a mixer before its scan, for ONE sequence ``x [T,
    E]``: ``(z [T, H_v, d], q [T, H_k, d], k, v [T, H_v, d], g [T, H_v],
    beta [T, H_v])``."""
    t = x.shape[0]
    hk, hv = arch["key_heads"], arch["value_heads"]
    h = _norm(x, p["norm"]["weight"], eps)
    qkvz = h @ _kernel(p, "in_proj_qkvz")
    d = qkvz.shape[1] // (2 * hk + 2 * hv)
    qkv, z = jnp.split(qkvz, [(2 * hk + hv) * d], axis=1)
    b, a = jnp.split(h @ _kernel(p, "in_proj_ba"), 2, axis=1)
    q, k, v = jnp.split(causal_conv(qkv, p["conv_kernel"]),
                        [hk * d, 2 * hk * d], axis=1)
    q = l2(q.reshape(t, hk, d)) * d ** -0.5
    k = l2(k.reshape(t, hk, d))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    return (z.reshape(t, hv, d), q, k, v.reshape(t, hv, d), g,
            jax.nn.sigmoid(b))


def _mixer_part(x, p, eps, arch, sizes):
    """``x + mixer(norm(x))`` for ONE sequence ``x [T, E]``, in three
    stages (up to the scan's inputs, the scan, from its output on), each
    under its own ``jax.checkpoint``."""
    t = x.shape[0]

    @jax.checkpoint
    def after(x, o, z, p):
        y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        y = p["gate_norm"] * y * jax.nn.silu(z)
        return x + y.reshape(t, -1) @ _kernel(p, "out_proj")

    z, q, k, v, g, beta = jax.checkpoint(
        lambda x, p: scan_inputs(x, p, eps, arch))(x, p)
    o = recurrence(q, k, v, g, beta, sizes["scan_segment"])
    return after(x, o, z, p)


# ---------------------------------------------------------------------------
# F: attention
# ---------------------------------------------------------------------------

def _rotate_first(x, arch, start=0):
    """The first ``rotary_dim`` elements of each head of ``[B, T, H, D]``
    at positions ``start ..``, the rest as they are."""
    r = arch["rotary_dim"]
    return jnp.concatenate([_rope(x[..., :r], arch["rope_theta"], start),
                            x[..., r:]], -1)


def _attention_part(x, p, n_head, eps, arch, sizes):
    """``x + attention(norm(x))`` for ``x [1, T, E]``."""
    b, t, _ = x.shape
    d = arch["head_dim"]
    kv = _kernel(p, "wk").shape[1] // d
    h = _norm(x, p["attn_norm"]["weight"], eps)
    k = _rotate_first(_norm((h @ _kernel(p, "wk")).reshape(b, t, kv, d),
                            p["k_norm"]["weight"], eps), arch)
    v = (h @ _kernel(p, "wv")).reshape(b, t, kv, d)

    def attend(start, hq):
        q, gate = jnp.split(hq @ _kernel(p, "wq"), 2, axis=-1)
        q = _rotate_first(_norm(q.reshape(b, -1, n_head, d),
                                p["q_norm"]["weight"], eps), arch, start)
        a = _attend(q, k, v, start, None).reshape(b, -1, n_head * d)
        return (a * jax.nn.sigmoid(gate)) @ _kernel(p, "wo")

    return x + _in_blocks(attend, sizes["query_block"], h)


# ---------------------------------------------------------------------------
# the expert MLP
# ---------------------------------------------------------------------------

def held_weights(h, p, arch, chosen=None):
    """``w [T, held]``: ``p_e / sum_{j in S} p_j`` for each held expert
    ``e`` in the token's chosen set ``S``, 0 elsewhere; and the router's
    own choice ``[T, k]`` with its scores ``[T, N]`` (a softmax over
    all)."""
    held = p["experts_gate"].shape[0]
    s = jax.nn.softmax(h @ p["router"], axis=-1)
    own = jax.lax.top_k(s, arch["top_k"])[1]
    chosen = own if chosen is None else chosen
    picked = jnp.take_along_axis(s, chosen, axis=1)            # [T, k]
    w = picked / picked.sum(-1, keepdims=True)
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


def _expert_part(x, p, eps, arch, chosen, sizes):
    """``x + sigmoid(w_s . h) shared(h) + routed(h)`` for ``x [1, T,
    E]``, and the router's own choice with its scores."""
    b, t, e = x.shape
    h = _norm(x, p["mlp_norm"]["weight"], eps)
    w_held, own = held_weights(h.reshape(b * t, e), p["moe"], arch, chosen)

    def mlp(_, hc, wc):
        shared = _swiglu(hc, _kernel(p, "shared_gate"),
                         _kernel(p, "shared_up"), _kernel(p, "shared_down"))
        routed = experts_under_mask(hc.reshape(-1, e),
                                    wc.reshape(-1, wc.shape[-1]), p["moe"])
        return jax.nn.sigmoid(hc @ _kernel(p, "shared_expert_gate")) \
            * shared + routed.reshape(hc.shape)

    return x + _in_blocks(mlp, sizes["token_chunk"], h,
                          w_held.reshape(b, t, -1)), own


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

def hidden(params: Dict[str, Any], tokens: jax.Array, *, n_layer: int,
           n_head: int, ln_eps: float, arch: Optional[Dict] = None,
           choices: Optional[List[jax.Array]] = None,
           query_block: int = 64, token_chunk: int = 256,
           scan_segment: int = 128, with_scores: bool = False):
    """(final normed hidden states ``[B, T, E]``, the float32 tree, the
    experts each layer's router chose ``[B*T, k]``; with ``with_scores``
    each of those a pair with the scores ``[B*T, N]``).  ``choices``: use
    THESE experts in place of the router's own top-k (the program's, to
    tell a flipped near tie from a wrong layer)."""
    arch = dict(ARCH, **(arch or {}))
    sizes = {"query_block": query_block, "token_chunk": token_chunk,
             "scan_segment": scan_segment}
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    pattern = arch["pattern"] or "L" * n_layer + "F"
    batch, seq = tokens.shape

    def layer(kind):
        """One layer over the batch, ONE SEQUENCE AT A TIME."""
        def run(x, p, given):
            def one(args):
                xi, mine = args
                if kind == "L":
                    xi = _mixer_part(xi, p["mixer"], ln_eps, arch, sizes)
                else:
                    xi = _attention_part(xi[None], p["attn"], n_head,
                                         ln_eps, arch, sizes)[0]
                out, own = _expert_part(xi[None], p["mlp"], ln_eps, arch,
                                        mine, sizes)
                return out[0], own
            return jax.lax.map(jax.checkpoint(one), (x, given))
        return jax.checkpoint(run)

    x = params["embed"][tokens]
    chose = []
    for i, kind in enumerate(pattern):
        given = None if choices is None \
            else choices[i].reshape(batch, seq, -1)
        x, own = layer(kind)(x, params[f"h{i}"], given)
        chose.append(tuple(a.reshape(batch * seq, -1) for a in own))
    x = _norm(x, params["final_norm"]["weight"], ln_eps)
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
