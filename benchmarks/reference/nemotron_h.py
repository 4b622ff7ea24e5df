"""The Nemotron-H stack as NVIDIA-Nemotron-3-Nano-30B-A3B configures it,
in plain ``jax.numpy``: forward, next-token loss and, through
``jax.grad``, gradients; float32 throughout,
``default_matmul_precision("highest")``, no kernels, NO CHUNKS, no
routing tables, no sorting.  It implements what the configuration's keys
and its ``assumed`` list fix (``benchmarks/configs/
nemotron-3-nano-30b-a3b.json``), for one sequence ``x [T, hidden]``;
every layer is ``x = x + part(RMSNorm(x))``, the part by the layer's
letter in the pattern:

* ``M``, the mixer.  ``[z | u | dt_raw] = W_in h`` (widths ``H P``, ``H P
  + 2 G N``, ``H``).  ``c_t = silu(b + sum_j w[j] * u_{t-3+j})``, four
  shifted products, ``u`` zero before the sequence; ``c`` split into ``xs
  [T, H, P]``, ``B``, ``C [T, G, N]``, head ``h`` reads group ``h // (H /
  G)``; ``dt = softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)``.  THE
  RECURRENCE, STEP BY STEP (``lax.scan`` over time; segments of it under
  ``jax.checkpoint`` so that its backward fits): ``S_t = exp(dt_t A)
  S_{t-1} + dt_t xs_t B_t^T``, ``y_t = S_t C_t + D xs_t``.  ``g = y *
  silu(z)``, RMS norm of ``g`` in ``G`` groups with one learned scale,
  ``W_out``;
* ``*``, attention: ``q = W_q h -> [T, heads, d]``, ``k, v -> [T, kv,
  d]``, query head ``n`` reads K/V head ``n // (heads / kv)``, causal
  softmax of ``q . k * d^-0.5`` a block of queries at a time, ``W_o``.
  No positional rotation;
* ``E``, the expert MLP: ``s = sigmoid(W_r h)`` over ALL published
  experts, ``S`` the ``k`` largest (one group: no group limit; the
  selection bias is zero at initialisation and left out), ``w_e =
  routed_scaling_factor * s_e / sum_{j in S} s_j``, result ``shared(h) +
  sum_{e in S and held here} w_e expert_e(h)``: each held expert applied
  to ALL tokens under the mask ``[e in S]``; every expert, the shared one
  too, ``W_down(relu(W_up h)^2)``;
* a final RMS norm, the untied head, next-token cross entropy over the
  vocabulary slice, no auxiliary term.

Departures, each on purpose, so that two sequences of 8,192 fit beside
the benchmark's training state: every layer runs the batch ONE SEQUENCE
AT A TIME (``lax.map``), attention's query side a block of positions at
a time against all keys, the MLPs and the head a chunk of tokens at a
time; every layer, sequence, block, chunk and scan segment is under
``jax.checkpoint`` when gradients are taken: recomputing changes no
arithmetic.

It takes the program's parameter tree (``embed``, ``head``,
``final_norm``; expert layers ``h<i>/mlp``, mixers ``m<i>/mixer``,
attention layers ``a<i>/attn``; every width is read from the tree's
shapes, ``n_head`` and ``arch``), the experts held (their count from the
tree, the first from ``arch``), and nothing else from the program.
``arch`` defaults to the configuration file's own keys; the pattern of
``n_layer`` expert layers is the share's ``(EM)^n *`` unless ``arch``
gives one.  RMS norm, the blocking helper, the score gap and the
gradient error are ``benchmarks/reference/afmoe.py``'s: the same plain
arithmetic for any model.
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
    grad_error,
    held_weights as _held_weights,
    score_gap,
)

#: |program loss - reference loss| / reference loss on one batch, each
#: with its OWN top-6 choices.  The program multiplies in bf16 with f32
#: accumulation and keeps a bf16 residual stream.  On the chip at the
#: cell's size (my chip runs, PR 35), twelve seeds: 1.1e-6 .. 1.3e-5 (at
#: an embedding of 0.5, nine seeds: 2.1e-6 .. 2.8e-5); the scatter is the
#: flips: 253-567 of a layer's 16,384 tokens choose another top-6 in bf16
#: than in f32.  The limit is 7.6 times the largest reading (the
#: driver draws fresh seeds) and Trinity-Mini's.  What it sees of the
#: controls: ``D xs`` left out 3.0e-4 and the shared expert left out
#: 2.0e-4; the rest read 3e-6 .. 8.5e-5 (norm before gate), as the sound
#: program does: at initial weights a mixer moves the loss little.  It
#: guards against a layer, the head or the labels gone wrong; the
#: gradients, the routing limit and the scan probe decide the rest.
LOSS_RTOL = 1e-4
#: ||g_program - g_reference|| / ||g_reference|| over the whole tree,
#: both at the reference's routing (``nemotron_h_paired.py``, which also
#: refuses a routing that is not the reference's up to near ties, and a
#: scan that is not the recurrence's in float32: the error then reads
#: exactly 1).  On the chip (my chip runs, PR 35): the program 0.006385
#: .. 0.006401 on five readings (0.0097 .. 0.0102 at its own routing;
#: 0.007887 .. 0.007893 on three seeds at an embedding of 0.5); the
#: parameters rounded to bfloat16, the nearest precision below and the
#: only control that routes and scans as the sound program does (0.03-
#: 0.05% misrouted), read 0.009347, 0.009630 and 0.009824 on three
#: seeds: the limit lies between, a factor 1.22 above the largest sound
#: reading and 1.20 below the smallest of that control (the check's
#: weights are ``PRNGKey(1)``'s whatever the seed, so the sound reading
#: moves in its fourth digit).  It was PLACED AFTER those readings: it
#: first stood at 0.011 (``LOSS_RTOL`` at 3e-4), which let bfloat16
#: parameters through (the controls' exit code 1, call 27); at the
#: limits as they stand ``controls/nemotron_h.py --seeds 2`` exits 0
#: (call 32).  Rotary positions on q and k read 0.0223; every other
#: control reads 1 through the routing limit or the scan probe.
GRAD_RTOL = 0.0078

_HERE = os.path.dirname(os.path.abspath(__file__))


def _arch_of_file() -> Dict[str, Any]:
    with open(os.path.join(os.path.dirname(_HERE), "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        conf = json.load(f)
    return {"route_scale": conf["routed_scaling_factor"],
            "top_k": conf["num_experts_per_tok"],
            "first_held": conf["as_run"]["experts_held"][0],
            "ssm_heads": conf["mamba_num_heads"],
            "ssm_groups": conf["n_groups"],
            "ssm_state": conf["ssm_state_size"],
            "head_dim": conf["head_dim"],
            "time_step_min": conf["time_step_min"],
            "time_step_max": conf["time_step_max"],
            "time_step_floor": conf["time_step_floor"],
            "published_layers": conf["published"]["num_hidden_layers"],
            "pattern": None}


ARCH = _arch_of_file()

#: the embedding's initial std.  This model has no muP factor, and a
#: token's own part of the residual stream has to stand out beside what
#: mixers and attention add, which is COMMON to neighbouring positions:
#: else every token prefers the same experts.  The share of choices that
#: land on the held sixteenth, a layer and a seed (my chip run, PR 35,
#: three seeds, 16,384 tokens; an even router gives 6.25%): 4.11-8.46% at
#: 0.02 (the largest expert up to 3.6 times the mean), 4.84-7.00% at 0.5
#: (2.1), 5.32-6.73% at 1.0 (1.6), 5.77-6.53% at 2.0 (1.3).  At 0.5,
#: Kanana-2's value, the cell's rate spread 0.70% over six seeds, over
#: the 0.5% a new cell is admitted under (PERF.md, PR 35)
EMBED_STD = 2.0

#: matrices that write into the residual stream: the source's
#: ``rescale_prenorm_residual`` scales them by ``1 / sqrt(layers)``
_OUT_PROJECTIONS = ("out_proj", "wo", "shared_down", "experts_down")


def init_like(shapes: Any, key: jax.Array) -> Any:
    """Random weights for a tree of shapes, by the source's initialisers
    (``assumed`` in the configuration file): ones for norm scales and
    ``D``; ``dt_bias`` the inverse softplus of a step drawn log-uniform
    in ``[time_step_min, time_step_max]`` and floored; ``A_log`` the log
    of a uniform draw in [1, 16]; N(0, 0.02) for every matrix, the
    convolution, its bias, the head, the router and the stacked experts,
    the projections that write into the residual stream scaled by ``1 /
    sqrt(published layers)``; N(0, ``EMBED_STD``) for the embedding.
    Leaves alike in name and shape are drawn as ONE stacked array."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: Dict[Any, list] = {}
    for i, (path, leaf) in enumerate(flat):
        names = [str(getattr(p, "key", p)) for p in path]
        name = names[-2] if names[-1] == "kernel" else names[-1]
        groups.setdefault((name, leaf.shape, leaf.dtype), []).append(i)
    out: list = [None] * len(flat)
    lo, hi = math.log(ARCH["time_step_min"]), math.log(ARCH["time_step_max"])
    for n, ((name, shape, dtype), where) in enumerate(groups.items()):
        full, k = (len(where), *shape), jax.random.fold_in(key, n)
        if name in ("scale", "D"):
            block = jnp.ones(full, dtype)
        elif name == "dt_bias":
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                k, full, jnp.float32, lo, hi)), ARCH["time_step_floor"])
            block = (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
        elif name == "A_log":
            block = jnp.log(jax.random.uniform(
                k, full, jnp.float32, 1.0, 16.0)).astype(dtype)
        else:
            std = EMBED_STD if name == "embed" else 0.02
            if name in _OUT_PROJECTIONS:
                std /= math.sqrt(ARCH["published_layers"])
            block = std * jax.random.normal(k, full, dtype)
        for j, i in enumerate(where):
            out[i] = block[j]
    return jax.tree_util.tree_unflatten(treedef, out)


def expand_layers(tree: Dict[str, Any], n_layer: int) -> Dict[str, Any]:
    """A depth-1 tree ``(h0, m0, a0)`` standing for the share's ``(EM)^n
    *``: ``h0`` .. ``h<n-1>`` and ``m0`` .. ``m<n-1>`` all alike; the
    attention layer, the embedding, the head and the final norm as they
    are."""
    out = {k: v for k, v in tree.items() if k not in ("h0", "m0")}
    for i in range(n_layer):
        out[f"h{i}"], out[f"m{i}"] = tree["h0"], tree["m0"]
    return out


def _kernel(p, name):
    return p[name]["kernel"]


def _relu2(h, p, prefix):
    up = jnp.maximum(h @ _kernel(p, prefix + "up"), 0.0)
    return (up * up) @ _kernel(p, prefix + "down")


# ---------------------------------------------------------------------------
# M: the mixer
# ---------------------------------------------------------------------------

def causal_conv(u, w, bias):
    """``silu(bias + sum_j w[j] * u[t - (taps - 1) + j])`` for ``u [T,
    C]``, ``w [taps, C]``: shifted products, zeros before the sequence."""
    taps, t = w.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1])), u])
    return jax.nn.silu(bias + sum(w[j] * padded[j:j + t]
                                  for j in range(taps)))


def recurrence(xs, dt, a, b, c, skip, segment: int = 128):
    """The selective state-space recurrence step by step for one
    sequence: ``xs [T, H, P]``, ``dt [T, H]``, ``a skip [H]``, ``b c [T,
    G, N]`` -> ``y [T, H, P]``.  ``segment`` steps under one
    ``jax.checkpoint``: a gradient keeps a state a segment, and a
    segment's states while it is differentiated."""
    t, heads, _ = xs.shape
    rep = heads // b.shape[1]
    segment = min(segment, t)
    assert t % segment == 0, (t, segment)

    def step(S, inp):
        x, d, bt, ct = inp
        bt, ct = jnp.repeat(bt, rep, 0), jnp.repeat(ct, rep, 0)   # [H, N]
        S = jnp.exp(d * a)[:, None, None] * S + \
            (d[:, None] * x)[:, :, None] * bt[:, None, :]
        return S, (S * ct[:, None, :]).sum(-1) + skip[:, None] * x

    def run(S, inp):
        return jax.lax.scan(step, S, inp)

    def cut(v):
        return v.reshape(t // segment, segment, *v.shape[1:])

    first = jnp.zeros((heads, xs.shape[2], b.shape[2]), jnp.float32)
    _, y = jax.lax.scan(jax.checkpoint(run), first,
                        (cut(xs), cut(dt), cut(b), cut(c)))
    return y.reshape(xs.shape)


def scan_inputs(x, p, eps, arch):
    """Everything of a mixer before its scan, for ONE sequence ``x [T,
    E]``: ``(z [T, H P], xs [T, H, P], dt [T, H], B [T, G, N], C [T, G,
    N])``."""
    t = x.shape[0]
    heads, groups, state = (arch["ssm_heads"], arch["ssm_groups"],
                            arch["ssm_state"])
    inner = p["gate_norm"]["scale"].shape[0]
    h = _rms(x, p["norm"]["scale"], eps)
    zxd = h @ _kernel(p, "in_proj")
    z, u, dt_raw = jnp.split(zxd, [inner, zxd.shape[1] - heads], axis=1)
    conv = causal_conv(u, p["conv_kernel"], p["conv_bias"])
    xs, b, c = jnp.split(conv, [inner, inner + groups * state], axis=1)
    return (z, xs.reshape(t, heads, -1),
            jax.nn.softplus(dt_raw + p["dt_bias"]),
            b.reshape(t, groups, state), c.reshape(t, groups, state))


def _mixer_part(x, p, eps, arch, sizes):
    """``x + mixer(norm(x))`` for ONE sequence ``x [T, E]``, in three
    stages (up to the scan's inputs, the scan, from its output on), each
    under its own ``jax.checkpoint``: a gradient holds one stage's
    float32 intermediates at a time."""
    t = x.shape[0]
    groups = arch["ssm_groups"]
    inner = p["gate_norm"]["scale"].shape[0]

    @jax.checkpoint
    def after(x, y, z, p):
        g = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
        return x + (g.reshape(t, inner) * p["gate_norm"]["scale"]) \
            @ _kernel(p, "out_proj")

    z, xs, dt, b, c = jax.checkpoint(
        lambda x, p: scan_inputs(x, p, eps, arch))(x, p)
    y = recurrence(xs, dt, -jnp.exp(p["A_log"]), b, c, p["D"],
                   sizes["scan_segment"])
    return after(x, y, z, p)


# ---------------------------------------------------------------------------
# *: attention
# ---------------------------------------------------------------------------

def _attention_part(x, p, n_head, eps, arch, sizes):
    """``x + attention(norm(x))`` for ``x [1, T, E]``."""
    b, t, _ = x.shape
    d = arch["head_dim"]
    kv = _kernel(p, "wk").shape[1] // d
    h = _rms(x, p["attn_norm"]["scale"], eps)
    k = (h @ _kernel(p, "wk")).reshape(b, t, kv, d)
    v = (h @ _kernel(p, "wv")).reshape(b, t, kv, d)

    def attend(start, hq):
        q = (hq @ _kernel(p, "wq")).reshape(b, -1, n_head, d)
        a = _attend(q, k, v, start, None)
        return a.reshape(b, -1, n_head * d) @ _kernel(p, "wo")

    return x + _in_blocks(attend, sizes["query_block"], h)


# ---------------------------------------------------------------------------
# E: the expert MLP
# ---------------------------------------------------------------------------

def held_weights(h, p, arch, chosen=None):
    """``w [T, held]``: ``route_scale * s_e / sum_{j in S} s_j`` for each
    held expert ``e`` in the token's chosen set ``S``, 0 elsewhere; and
    the router's own choice ``[T, k]`` with its scores ``[T, N]``:
    ``reference/afmoe.py``'s, which counts the held experts on the gate
    matrices an expert of this form does not have."""
    return _held_weights(h, {"router": p["router"],
                             "experts_gate": p["experts_up"]}, arch, chosen)


def experts_under_mask(h, w_held, p):
    """``sum_e w_e[t] expert_e(h[t])``: every held expert applied to
    every token ``h [T, E]``, weighted by ``w_held [T, held]``."""
    up = jnp.maximum(jnp.einsum("te,fem->ftm", h, p["experts_up"]), 0.0)
    y = jnp.einsum("ftm,fme->fte", up * up, p["experts_down"])
    return jnp.einsum("tf,fte->te", w_held, y)


def _expert_part(x, p, eps, arch, chosen, sizes):
    """``x + shared(norm(x)) + routed(norm(x))`` for ``x [1, T, E]``, and
    the router's own choice with its scores."""
    b, t, e = x.shape
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    w_held, own = held_weights(h.reshape(b * t, e), p["moe"], arch, chosen)

    def mlp(_, hc, wc):
        routed = experts_under_mask(hc.reshape(-1, e),
                                    wc.reshape(-1, wc.shape[-1]), p["moe"])
        return _relu2(hc, p, "shared_") + routed.reshape(hc.shape)

    return x + _in_blocks(mlp, sizes["token_chunk"], h,
                          w_held.reshape(b, t, -1)), own


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

_PREFIX = {"M": "m", "E": "h", "*": "a"}


def hidden(params: Dict[str, Any], tokens: jax.Array, *, n_layer: int,
           n_head: int, ln_eps: float, arch: Optional[Dict] = None,
           choices: Optional[List[jax.Array]] = None,
           query_block: int = 64, token_chunk: int = 256,
           scan_segment: int = 128, with_scores: bool = False):
    """(final normed hidden states ``[B, T, E]``, the float32 tree, the
    experts each expert layer's router chose ``[B*T, k]``; with
    ``with_scores`` each of those a pair with the scores ``[B*T, N]``).
    ``choices``: use THESE experts in place of the router's own top-k
    (the program's, to tell a flipped near tie from a wrong layer)."""
    arch = dict(ARCH, **(arch or {}))
    sizes = {"query_block": query_block, "token_chunk": token_chunk,
             "scan_segment": scan_segment}
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    pattern = arch["pattern"] or "EM" * n_layer + "*"
    batch, seq = tokens.shape

    def layer(kind):
        """One layer over the batch, ONE SEQUENCE AT A TIME."""
        def run(x, p, given):
            def one(args):
                xi, mine = args
                if kind == "M":
                    return _mixer_part(xi, p["mixer"], ln_eps, arch,
                                       sizes), None
                if kind == "*":
                    return _attention_part(xi[None], p["attn"], n_head,
                                           ln_eps, arch, sizes)[0], None
                out, own = _expert_part(xi[None], p["mlp"], ln_eps, arch,
                                        mine, sizes)
                return out[0], own
            return jax.lax.map(jax.checkpoint(one), (x, given))
        return jax.checkpoint(run)

    x = params["embed"][tokens]
    count = {"M": 0, "E": 0, "*": 0}
    chose = []
    for kind in pattern:
        i, count[kind] = count[kind], count[kind] + 1
        given = None
        if kind == "E" and choices is not None:
            given = choices[i].reshape(batch, seq, -1)
        x, own = layer(kind)(x, params[f"{_PREFIX[kind]}{i}"], given)
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
