"""Mellum decoder (JetBrains Mellum 2, ``model_type`` ``mellum``) in flax
linen, for the training path: the first model here whose routed layer
crosses chips.

What the block is, as the public ``config.json`` keys fix it (what they
leave open is listed under ``assumed`` in ``benchmarks/configs/
mellum2-12b-a2.5b.json``):

* pre-norm only: ``a = x + attn(norm(x))``, ``y = a + moe(norm(a))``;
  no dense layer, no shared expert: every MLP is the routed one;
* attention: grouped heads (``num_kv_heads`` K/V heads serve
  ``num_heads`` query heads inside the flash kernels), no bias, no norm
  on q or k, no output gate; q and k rotated over the whole head, halves
  rotated, with a table CHOSEN BY THE LAYER'S KIND (:func:`rope_table`):
  ``sliding_attention`` layers (window ``window``) the plain one,
  ``full_attention`` layers YaRN's (the frequencies blended between
  extrapolation and interpolation by ``factor``, and cos and sin both
  times ``attention_factor``, so the scores carry its square);
* the routed layer IS ``models/afmoe.py``'s (:class:`RoutedExperts`) with
  a SOFTMAX router: scores a softmax over all published experts, the
  ``top_k`` largest, renormalised over the chosen, no scale, no bias;
* untied embedding and head, a final RMS norm; no auxiliary loss term.

**Four chips share each layer** (``expert_axis``): under a global mesh
with several chips along that axis the experts lie over it by expert
(``parallel/sharding.py`` ``FSDP_EP_RULES``), a layer call sees ONE
SEQUENCE OF EVERY CHIP, and the routed layer runs with its exchange
(``parallel/expert.py``).  Everything else lies as under FSDP: GSPMD
gathers a weight for its use, and what GSPMD cannot partition (the flash
kernels, the fused norms, the chunked head) runs per batch shard through
its own ``mesh=`` seam.  On one device the same model holds all its
experts and runs without the exchange.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.core import telemetry
from ray_tpu.models import afmoe, step
from ray_tpu.models.afmoe import RoutedExperts, _dense, router_stats  # noqa: F401
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.fused import fused_rmsnorm
from ray_tpu.parallel import expert
from ray_tpu.parallel.mesh import get_global_mesh
from ray_tpu.parallel.sharding import constrain_activation


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    #: the sequence as run (``max_position_embeddings``, 131072, only
    #: bounds it: the rotation tables are made for the sequence)
    max_seq_len: int = 8192
    num_layers: int = 28
    #: published index AFTER the last layer as run (``None``: the last
    #: of all): a model cut in depth is the ``num_layers`` layers that
    #: END there, so a stage cut further (the benchmark's gradient check
    #: runs two layers of it) keeps the period's full layer
    layer_stop: Optional[int] = None
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    embed_dim: int = 2304
    expert_dim: int = 896
    #: the router's width: all published experts
    num_experts: int = 64
    top_k: int = 8
    #: (first, count): the contiguous share of the experts held by the
    #: GROUP of chips along ``expert_axis`` (one chip where there is none)
    experts_held: Tuple[int, int] = (0, 64)
    #: the mesh axis the experts lie over; the chips along it share each
    #: layer and exchange tokens for it
    expert_axis: Optional[str] = "fsdp"
    score_func: str = "softmax"
    #: ``norm_topk_prob`` and nothing else: no scaling factor
    route_scale: float = 1.0
    #: tokens of a chip that ONE call of the routed layer sees (``None``:
    #: a whole sequence).  The layer's row buffers are sized for the
    #: worst case, every choice of every token of the GROUP's call
    #: landing on one chip, four times what an even router lands there:
    #: at 8,192 the step's temporaries do not fit beside the state
    routed_tokens: Optional[int] = None
    window: int = 1024
    #: every n-th layer (published index + 1 divisible by n) is full
    global_every: int = 4
    rope_theta: float = 500000.0
    #: YaRN, the full layers' rotation (``rope_parameters.full_attention``)
    yarn_factor: float = 16.0
    yarn_original_max: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: scores, top-k and weights; float32
    router_dtype: Any = jnp.float32
    #: "" | "full": each part of a layer recomputed in the backward pass
    remat: str = ""

    @classmethod
    def mellum2_12b_a2_5b(cls, **kw) -> "MellumConfig":  # 12B, 2.5B active
        return cls(**kw)

    @classmethod
    def mellum2_12b_a2_5b_stage(cls, **kw) -> "MellumConfig":
        """One pipeline stage of seven, on the four chips that share
        each of its layers (``benchmarks/configs/mellum2-12b-a2.5b.
        json``): the whole period of layers 4..7 (sliding, sliding,
        sliding, full), every layer whole, all 64 experts, the whole
        vocabulary; every width as published."""
        defaults = dict(num_layers=4, layer_stop=8, max_seq_len=8192,
                        routed_tokens=4096)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "MellumConfig":  # for tests
        defaults = dict(vocab_size=256, max_seq_len=64, num_layers=4,
                        num_heads=4, num_kv_heads=2, head_dim=16,
                        embed_dim=32, expert_dim=16, num_experts=8,
                        top_k=2, experts_held=(0, 8), window=24,
                        yarn_original_max=32)
        defaults.update(kw)
        return cls(**defaults)

    @property
    def kv_heads(self) -> int:
        return math.gcd(self.num_heads, self.num_kv_heads)

    def layer_kinds(self) -> List[str]:
        """``sliding`` or ``full`` for every layer as run, by the
        published index of each."""
        stop = self.num_layers if self.layer_stop is None \
            else self.layer_stop
        return ["full" if (j + 1) % self.global_every == 0 else "sliding"
                for j in range(stop - self.num_layers, stop)]

    def plan_args(self, tokens: int) -> Dict[str, Any]:
        """What was compiled, for the ``moe.plan`` span; ``tokens``: a
        sequence's (the row bound and the pairs are of one call of a
        routed layer, the GROUP's where it runs with its exchange)."""
        tokens = min(self.routed_tokens or tokens, tokens)
        mesh = expert.group_mesh(self.expert_axis)
        chips = mesh.shape[self.expert_axis] if mesh is not None else 1
        held = dataclasses.replace(self, experts_held=(
            self.experts_held[0], self.experts_held[1] // chips))
        args = afmoe.routed_plan_args(held, chips * tokens)
        # a chip keeps the choices of the tokens it routes itself, and
        # the plan over the group's
        args["kept_bytes"] -= (chips - 1) * tokens * self.top_k * 4
        return {**args,
                "router": self.score_func, "window": self.window,
                "heads": self.num_heads, "kv_heads": self.kv_heads,
                "layers": ",".join(k[0] for k in self.layer_kinds())}


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """``(inv_freq [dim / 2]`` float32, ``low``, ``high)``: YaRN's
    frequencies over a rotary width ``dim`` (this model's and
    ``models/deepseek_v3.py``'s).  Pair ``j`` turns ``theta^(-2j/dim)`` a
    position where it makes more than ``beta_fast`` turns over the
    original context (``j <= low``: extrapolated, as trained), that over
    ``factor`` where it makes fewer than ``beta_slow`` (``j >= high``:
    interpolated), and a linear blend between."""
    def pair_of(turns: float) -> float:
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim // 2 - 1)
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * j / dim)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * inv + ramp * inv / factor, low, high


def rope_table(cfg: MellumConfig, kind: str, seq: int):
    """``(cos, sin) [seq, head_dim / 2]`` float32 for positions ``0 ..
    seq - 1`` of a layer of ``kind``: sliding layers the plain table at
    ``rope_theta``; full layers YaRN's, cos and sin BOTH times
    ``attention_factor``.  The table does not depend on the sequence run
    (``original_max_position_embeddings`` enters through ``low`` and
    ``high`` alone)."""
    if kind == "full":
        inv = yarn_inv_freq(cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
                            cfg.yarn_original_max, cfg.yarn_beta_fast,
                            cfg.yarn_beta_slow)[0]
        factor = cfg.yarn_attention_factor
    else:
        j = jnp.arange(cfg.head_dim // 2, dtype=jnp.float32)
        inv, factor = cfg.rope_theta ** (-2.0 * j / cfg.head_dim), 1.0
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None]
    return factor * jnp.cos(angles), factor * jnp.sin(angles)


def rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x [B, T, H, D]`` with the pairs ``(x[i], x[i + D/2])`` rotated
    (``afmoe._rope``'s convention) by the table ``[T, D/2]``, in
    float32, rounded once to ``x``'s dtype.  Each half is widened after
    the split and rounded before the two are joined: XLA moves a
    ``convert`` of the joined halves there itself (and its transpose
    likewise), and the fusion it then roots in a ``convert`` of its own
    making runs under no name, so the step's parts lose it."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = (half.astype(jnp.float32)
              for half in jnp.split(x, 2, axis=-1))
    return jnp.concatenate([(x1 * cos - x2 * sin).astype(x.dtype),
                            (x1 * sin + x2 * cos).astype(x.dtype)], axis=-1)


class Norm(nn.Module):
    """``llama.RMSNorm`` whose kernel runs per batch shard under a mesh
    (``fused_rmsnorm(mesh=)``)."""
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.with_partitioning(
            nn.initializers.ones, ("embed",)), (x.shape[-1],), jnp.float32)
        return fused_rmsnorm(x, w, eps=self.eps, mesh=get_global_mesh())


def _here(x: jax.Array) -> jax.Array:
    """Under a mesh an activation lies on its batch shard, whole along
    ``embed``: weights are gathered for it, it is not exchanged."""
    return constrain_activation(x, "batch", "seq", "embed")


class AttentionPart(nn.Module):
    """``x + attention(norm(x))``.  A block names it ``attn``, and flax
    puts a module's name around its ops: that IS the step's part
    ``attn`` (``models/step.py``)."""
    config: MellumConfig
    kind: str      # "sliding" | "full"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        batch, seq = x.shape[:2]
        heads, kv, dim = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        # the part's pieces (``step.ATTN_PIECES``); the kernels' call
        # names its own two inside its kind
        with step.scope("attn.norm"):
            h = Norm(cfg.rms_eps, name="attn_norm")(x)
        with step.scope("attn.proj"):
            q = _dense(cfg, heads * dim, "wq", ("embed", "heads"))(h)
            k = _dense(cfg, kv * dim, "wk", ("embed", "kv"))(h)
            v = _dense(cfg, kv * dim, "wv", ("embed", "kv"))(h)
        with step.scope("attn.pos"):  # (a view by heads moves nothing)
            table = rope_table(cfg, self.kind, seq)
            q = rotate(q.reshape(batch, seq, heads, dim), *table)
            k = rotate(k.reshape(batch, seq, kv, dim), *table)
            v = v.reshape(batch, seq, kv, dim)
        with step.scope("attn." + self.kind):
            attn = flash_attention(
                q, k, v, causal=True, mesh=get_global_mesh(),
                window=cfg.window if self.kind == "sliding" else None)
        with step.scope("attn.proj"):
            attn = _dense(cfg, cfg.embed_dim, "wo", ("heads", "embed"))(
                attn.reshape(batch, seq, heads * dim))
        with step.scope("attn.norm"):
            return _here(x + attn)


class MLPPart(nn.Module):
    """``x + moe(norm(x))``: the routed experts and nothing beside them.
    The norm and the residual add are the step's part ``mlp``, the
    routed layer its own parts BESIDE it (``moe.exchange`` among them
    where the layer crosses chips), so the module names its parts itself
    (``step.names_its_parts``)."""
    config: MellumConfig
    names_its_parts = True

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        with step.named_children():
            with step.scope("mlp"):
                h = Norm(cfg.rms_eps, name="mlp_norm")(x)
            routed = RoutedExperts(cfg, name="moe")(h, chosen)
            with step.scope("mlp"):
                return _here(x + routed)


def sequences_a_call() -> int:
    """Sequences ONE call of a layer sees: one of every chip the batch
    is split over (one, with no mesh)."""
    mesh = get_global_mesh()
    if mesh is None:
        return 1
    return math.prod(mesh.shape.get(a, 1) for a in ("dp", "fsdp"))


class MellumBlock(nn.Module):
    """One layer: its two parts, each over ONE SEQUENCE OF EVERY CHIP at
    a time (a kernel call a sequence, the activation memory of one
    sequence a chip) and each recomputed on its own in the backward pass
    under ``remat``, as ``afmoe.AFMoEBlock``; the routed part, which
    sees a token at a time, over ``routed_tokens`` of the sequence a
    call (an exchange a call).  What a routed call DECIDED is kept, not
    recomputed (``step.remat``): a chip's own choices and its plan over
    the GROUP's pairs, 1.46 MB a call at the cell's shapes, so the
    recompute gathers no choices, sorts nothing and takes no ``top_k``."""
    config: MellumConfig
    kind: str      # "sliding" | "full"

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        attn, mlp = AttentionPart, MLPPart
        if cfg.remat == "full":
            attn, mlp = step.remat(attn), step.remat(mlp)
        attn, mlp = attn(cfg, self.kind, name="attn"), mlp(cfg, name="mlp")
        group = sequences_a_call()
        batch, seq, embed = x.shape
        if batch % group:
            raise ValueError(f"a batch of {batch} does not give each of "
                             f"{group} chips whole sequences")
        # (of ``attn``, the piece that holds the residual stream's side)
        with step.scope("attn.norm"):
            # a chip's sequences are neighbours in the batch
            calls = x.reshape(group, batch // group, seq, embed)
        if chosen is not None:
            chosen = chosen.reshape(group, batch // group, seq, -1)
        piece = min(cfg.routed_tokens or seq, seq)
        out = []
        for i in range(batch // group):
            with step.scope("attn.norm"):
                h = _here(calls[:, i])
            h = attn(h)
            pieces = []
            for lo in range(0, seq, piece):
                with step.scope("mlp"):
                    part = _here(h[:, lo:lo + piece])
                with step.names_its_parts(mlp):
                    pieces.append(mlp(part) if chosen is None else mlp(
                        part, chosen[:, i, lo:lo + piece].reshape(
                            group * piece, -1)))
            with step.scope("mlp"):
                out.append(jnp.concatenate(pieces, axis=1))
        with step.scope("mlp"):
            return _here(jnp.stack(out, axis=1).reshape(x.shape))


class Mellum(nn.Module):
    config: MellumConfig

    @nn.compact
    def hidden(self, tokens: jax.Array,
               choices: Optional[List[jax.Array]] = None):
        """Final normed hidden states (float32) and the untied head
        ``[V, E]``, as ``afmoe.AFMoE.hidden`` (``choices``: a recorded
        routing ``[B*T, k]`` a layer to replay)."""
        cfg = self.config

        def table(name):
            return self.param(
                name, nn.with_partitioning(nn.initializers.normal(0.02),
                                           ("vocab", "embed")),
                (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)

        embed, head = table("embed"), table("head")
        with step.scope("embed"):
            # the lookup is a use of the table like any other: the
            # rounded table is gathered along embed for it
            x = _here(constrain_activation(
                embed.astype(cfg.dtype), "vocab", "embed")[tokens])
        # the timeline says what was compiled: spans around the trace of
        # the layers (a call of a layer sees one sequence a chip)
        seq = tokens.shape[1]
        with telemetry.span("model", "moe.plan", **cfg.plan_args(seq)), \
                expert.ep_plan(
                    cfg.expert_axis, experts=cfg.experts_held[1],
                    tokens_local=min(cfg.routed_tokens or seq, seq),
                    embed=cfg.embed_dim, top_k=cfg.top_k,
                    itemsize=jnp.dtype(cfg.dtype).itemsize):
            for i, kind in enumerate(cfg.layer_kinds()):
                block = MellumBlock(cfg, kind, name=f"h{i}")
                x = block(x) if choices is None else block(x, choices[i])
        # the final norm is the head's: ``loss_fn`` opens the part again
        with step.scope("head"):
            x = Norm(cfg.rms_eps, name="final_norm")(x)
            return _here(x.astype(jnp.float32)), head

    def __call__(self, tokens: jax.Array) -> jax.Array:
        x, head = self.hidden(tokens)
        return jnp.einsum("bte,ve->btv", x, head.astype(jnp.float32))

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: Optional[int] = None):
        seq = seq or self.config.max_seq_len
        tokens = jnp.zeros((batch, seq), jnp.int32)
        return self.init(rng, tokens)["params"]


def _own_choices(model: Mellum, state, batch: int) -> List[jax.Array]:
    """What every layer's router chose ITSELF, ``[B*T, k]`` in batch
    order: sown once a call, and a call sees a piece of one sequence of
    every chip (a chip's sequences one after the other, a sequence's
    pieces in order)."""
    group = sequences_a_call()
    out = []
    for i in range(model.config.num_layers):
        calls = jnp.stack(       # [sequences a chip x pieces, group * p, k]
            state["intermediates"][f"h{i}"]["mlp"]["moe"]["expert_choice"])
        k = calls.shape[-1]
        by_chip = calls.reshape(batch // group, -1, group,
                                calls.shape[1] // group, k)
        out.append(jnp.moveaxis(by_chip, 2, 0).reshape(-1, k))
    return out


def loss_fn(model: Mellum, params, tokens: jax.Array,
            head_chunk: int = 2048,
            head_logits_dtype: Any = None,
            choices: Optional[List[jax.Array]] = None,
            with_choices: bool = False):
    """Next-token cross entropy over the whole vocabulary through the
    chunked LM head, as ``afmoe.loss_fn`` (``choices``: a recorded
    routing to replay; ``with_choices``: also the routers' own); under a
    mesh every chip cuts the head's chunks inside its own tokens."""
    from ray_tpu.ops.fused import chunked_lm_loss

    out = model.apply({"params": params}, tokens, choices,
                      method=Mellum.hidden,
                      mutable=["intermediates"] if with_choices else False)
    (x, head), state = out if with_choices else (out, None)
    compute = jnp.bfloat16 if model.config.dtype == jnp.bfloat16 else None
    # the scan's body inherits the name, and the gradient's two products
    # with it: they run in the forward's scan (``ops/fused.py``)
    with step.scope("head"):
        loss = chunked_lm_loss(x[:, :-1], head, tokens[:, 1:],
                               chunk=head_chunk, compute_dtype=compute,
                               logits_dtype=head_logits_dtype,
                               mesh=get_global_mesh())
    if not with_choices:
        return loss
    return loss, _own_choices(model, state, tokens.shape[0])


def make_train_step(model: Mellum, tx):
    """The donated ``(params, opt_state, tokens) -> (params, opt_state,
    loss)`` step, GPT-2's (``models/step.py``)."""
    return step.make_train_step(functools.partial(loss_fn, model), tx,
                                remat=model.config.remat)


@functools.partial(jax.jit, static_argnums=0)
def router_choices(model: Mellum, params, tokens: jax.Array
                   ) -> List[jax.Array]:
    """The experts every layer's router chose, ``[B*T, k]`` a layer in
    batch order."""
    _, state = model.apply({"params": params}, tokens,
                           method=Mellum.hidden, mutable=["intermediates"])
    return _own_choices(model, state, tokens.shape[0])


def group_stats(model: Mellum, stats: Dict[str, Any], chips: int,
                tokens: int) -> Dict[str, Any]:
    """``afmoe.router_stats`` over a batch of ``tokens`` tokens of a
    model whose routed layers cross ``chips`` chips, as the chips see
    it: ``landed_share [L]`` the share of the GROUP's pairs that arrived
    on the FULLEST chip (``1 / chips`` at an even router: what PR 48's
    walks and the grouped products cost there, and the chip the others
    wait for), and ``exchange_bytes`` one chip receives and sends for
    the layers' calls of one forward pass over the batch."""
    cfg = model.config
    load = stats["load"]                       # [L, held by the group]
    arrived = load.reshape(load.shape[0], chips, -1).sum(-1)
    one = expert.exchange_bytes(chips, cfg.max_seq_len, cfg.embed_dim,
                                cfg.top_k, jnp.dtype(cfg.dtype).itemsize)
    calls = cfg.num_layers * tokens // (chips * cfg.max_seq_len)
    return {**stats,
            "landed_share": (arrived / load.sum(-1, keepdims=True)).max(-1),
            "exchange_bytes": calls * (one["gather_bytes"]
                                       + one["scatter_bytes"])}


def report_router_stats(stats: Dict[str, Any]) -> Dict[str, float]:
    """The ``ray_tpu_moe_*`` gauges under this model's name
    (``afmoe.report_router_stats``) and, where :func:`group_stats` added
    it, ``ray_tpu_moe_exchange_bytes``."""
    out = afmoe.report_router_stats(stats, model_name="mellum")
    if "exchange_bytes" in stats:
        telemetry.moe_exchange_bytes("mellum", stats["exchange_bytes"])
        out["moe/exchange_bytes"] = float(stats["exchange_bytes"])
    return out
