"""DeepSeek-V3-style decoder (``model_type`` ``deepseek_v3`` and its
kin; the benchmark runs kakaocorp's Kanana-2-30B-A3B and XingChen-AGI's
Xing4.0-29B-A4B, ``model_type`` ``xing4_0``) in flax linen, for the
training path.

What the block is, as the public ``config.json`` keys fix it (what they
leave open is listed under ``assumed`` in ``benchmarks/configs/
kanana-2-30b-a3b.json`` and ``xing4.0-29b-a4b.json``).  The defaults of
:class:`DeepseekV3Config` are Kanana's, and with them the step is the
program it was before the family's other mechanisms came:

* latent attention (MLA): ``W_kva h`` gives a latent of ``kv_lora_rank``
  (RMS-normalised, its own learned scale) and ONE rotary key head of
  ``rope`` that every query head shares; ``W_kvb`` lifts the latent to
  each head's own key part (``nope``) and value (``v_head_dim``).  The
  query is ``W_q h``, ``nope + rope`` wide a head (Kanana:
  ``q_lora_rank`` null), or comes through a latent of its own
  (``q_lora_rank``: ``q = W_qb N(W_qa h)``, the norm with its own
  scale).  RoPE, pairs interleaved, on q's rotary part and on the shared
  key only, plain (Kanana: ``rope_scaling`` null) or with YaRN's
  frequencies (``yarn_factor``; ``models/mellum.py``
  ``yarn_inv_freq``), cos and sin times ``m(mscale) /
  m(mscale_all_dim)``.  Scores scaled by ``(nope + rope) ** -0.5``,
  under YaRN times ``m(mscale_all_dim)^2`` (DeepSeek-V3's ``mscale``
  enters the softmax scale, which the kernels take as ``scale=``);
  causal, no window.  The flash kernels take the key in its two parts
  (``flash_attention(k_rope=)``): nothing is broadcast or padded in HBM;
* pre-norm only.  ONE lane (Kanana: ``hc_mult`` 1): ``x + attn(norm(
  x))``, then ``x + mlp(norm(x))``.  Several (``hc_mult`` 4): a part
  computes ``F(N(u))`` all the same and ``models/hyper.py`` reads ``u``
  from the lanes and writes the result back (:func:`hyper.residual`);
* leading dense layers with a SwiGLU MLP, then expert layers: the routed
  layer IS ``models/afmoe.py``'s (:class:`RoutedExperts`: sigmoid scores
  in float32 over all published experts, the ``top_k`` largest, weights
  normalised over the chosen and scaled by ``route_scale``, a layer told
  which experts it holds, dropless grouped products) at this model's
  numbers, beside the shared experts as one SwiGLU of their joint width;
* untied embedding and head, a final RMS norm; no auxiliary loss term;
* no multi-token head (Kanana: ``num_mtp_layers`` 0), or DeepSeek-V3's
  module of depth 1 (:class:`MultiToken`): ``W_M [N(h_t) ; N(E[token_{t
  + 1}])]`` through one expert layer of its own, its own norm and the
  SHARED head, and a second term in the loss (:func:`loss_fn`).

The source's selection bias (``e_score_correction_bias``, updated
outside the gradient, zero at initialisation) is left out, as in
``afmoe.py``.  Every layer runs its two parts over one sequence of the
batch at a time, each recomputed on its own under ``remat`` (a routed
call's choices and row plan kept: ``models/step.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.core import telemetry
from ray_tpu.models import afmoe, hyper, step
from ray_tpu.models.afmoe import (  # noqa: F401 — this model's layer too
    RoutedExperts,
    _dense,
    _HeadNorm,
    _swiglu,
    each_sequence,
    router_stats,
)
from ray_tpu.models.llama import RMSNorm
from ray_tpu.models.mellum import yarn_inv_freq
from ray_tpu.ops.flash_attention import flash_attention

#: the query latent's norm (a name of its own: a control takes it out)
_QueryNorm = _HeadNorm

#: YaRN beside ``yarn_factor``, as the one configuration that has it
#: publishes them (``rope_scaling``: ``original_max_position_embeddings``,
#: ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``); fields
#: when a second configuration says otherwise
YARN_ORIGINAL_MAX = 4096
YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0
YARN_MSCALE, YARN_MSCALE_ALL_DIM = 1.0, 1.0
#: the weight of the multi-token module's term in the loss (``assumed``)
MTP_WEIGHT = 0.3

#: the ``ray_tpu_moe_*`` gauges under this model's name
report_router_stats = functools.partial(afmoe.report_router_stats,
                                        model_name="deepseek_v3")


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    #: the sequence as run (``max_position_embeddings``, 32768, only
    #: bounds it: RoPE needs no table)
    max_seq_len: int = 16384
    #: EXPERT layers; the leading dense layers are counted apart
    num_layers: int = 47
    num_dense_layers: int = 1
    num_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    #: the query's latent (``None``: ``q = W_q h``)
    q_lora_rank: Optional[int] = None
    embed_dim: int = 2048
    dense_dim: int = 6144
    expert_dim: int = 768
    num_shared_experts: int = 2
    #: the router's width: all published experts, held here or not
    num_experts: int = 128
    top_k: int = 6
    #: (first, count): the contiguous share of the experts held here
    experts_held: Tuple[int, int] = (0, 128)
    route_scale: float = 2.448
    rope_theta: float = 1e6
    #: YaRN (``rope_scaling`` of type ``yarn``; ``None``: plain RoPE);
    #: its other constants: ``YARN_*`` above
    yarn_factor: Optional[float] = None
    #: lanes of the residual stream (1: the plain residual) under
    #: hyper-connections (``models/hyper.py``, whose constants are the
    #: iteration's count, epsilon and clamp)
    hc_mult: int = 1
    #: multi-token modules after the last layer (0 or 1)
    num_mtp_layers: int = 0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: scores, top-k and weights; float32 as the source's router
    router_dtype: Any = jnp.float32
    #: "" | "full": each part of a layer recomputed in the backward pass
    remat: str = ""

    @classmethod
    def kanana_2_30b_a3b(cls, **kw) -> "DeepseekV3Config":  # 30B, 3B active
        return cls(**kw)

    @classmethod
    def kanana_2_30b_a3b_share(cls, **kw) -> "DeepseekV3Config":
        """One chip's share of eight (``benchmarks/configs/
        kanana-2-30b-a3b.json``): the dense layer and five expert layers
        of 48, 16 of 128 experts, 16,032 of 128,256 vocabulary rows;
        every width as published."""
        defaults = dict(num_layers=5, experts_held=(0, 16),
                        vocab_size=16032, max_seq_len=16384)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def xing4_0_29b_a4b(cls, **kw) -> "DeepseekV3Config":  # 29B, 4B active
        """Xing4.0-29B-A4B as published: 2 dense and 38 expert layers,
        a query latent, YaRN, four lanes, one multi-token module."""
        defaults = dict(
            vocab_size=131072, max_seq_len=4096, num_layers=38,
            num_dense_layers=2, embed_dim=3584, dense_dim=9216,
            expert_dim=1024, num_shared_experts=1, num_experts=64, top_k=4,
            experts_held=(0, 64), route_scale=2.0, q_lora_rank=768,
            rope_theta=1e4, yarn_factor=64.0, hc_mult=4, num_mtp_layers=1)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def xing4_0_29b_a4b_share(cls, **kw) -> "DeepseekV3Config":
        """One chip's share of eight (``benchmarks/configs/
        xing4.0-29b-a4b.json``): ONE of the two leading dense layers and
        four expert layers of 38, 8 of 64 experts, 16,384 of 131,072
        vocabulary rows, no multi-token module (it lies on the
        pipeline's last stage), sequences of 2,048; every width as
        published."""
        defaults = dict(num_layers=4, num_dense_layers=1,
                        experts_held=(0, 8), vocab_size=16384,
                        max_seq_len=2048, num_mtp_layers=0)
        defaults.update(kw)
        return cls.xing4_0_29b_a4b(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "DeepseekV3Config":  # for tests
        defaults = dict(vocab_size=256, max_seq_len=64, num_layers=2,
                        num_heads=4, qk_nope_dim=16, qk_rope_dim=8,
                        v_head_dim=16, kv_lora_rank=32, embed_dim=32,
                        dense_dim=64, expert_dim=16, num_experts=8,
                        top_k=2, experts_held=(0, 8))
        defaults.update(kw)
        return cls(**defaults)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    def yarn_m(self, mscale: float) -> float:
        """YaRN's ``0.1 mscale ln(factor) + 1`` (1 without YaRN)."""
        if self.yarn_factor is None or self.yarn_factor <= 1:
            return 1.0
        return 0.1 * mscale * math.log(self.yarn_factor) + 1.0

    @property
    def softmax_scale(self) -> Optional[float]:
        """``None`` (the kernels' own ``(nope + rope) ** -0.5``) without
        YaRN; under it that times ``m(mscale_all_dim)^2``."""
        if self.yarn_factor is None:
            return None
        return self.qk_head_dim ** -0.5 * self.yarn_m(
            YARN_MSCALE_ALL_DIM) ** 2

    def rope_table(self):
        """``None`` without YaRN (:func:`rope_interleaved` makes the
        plain frequencies itself), else ``(inv_freq [rope / 2], factor)``:
        YaRN's frequencies over the rotary part and what cos and sin are
        both multiplied by."""
        if self.yarn_factor is None:
            return None
        inv = yarn_inv_freq(self.qk_rope_dim, self.rope_theta,
                            self.yarn_factor, YARN_ORIGINAL_MAX,
                            YARN_BETA_FAST, YARN_BETA_SLOW)[0]
        return inv, self.yarn_m(YARN_MSCALE) / self.yarn_m(
            YARN_MSCALE_ALL_DIM)

    def plan_args(self, tokens: int) -> Dict[str, Any]:
        """What attention was compiled, for the ``mla.plan`` span: the
        widths, and which kernels carry them (the head-major family,
        since a ``nope + rope``-wide head fills no whole 128-lane slabs;
        ``concat``: a tile's two key parts joined along lanes, so one
        ``nope + rope``-deep score product)."""
        args = {"heads": self.num_heads, "nope": self.qk_nope_dim,
                "rope": self.qk_rope_dim, "value": self.v_head_dim,
                "latent": self.kv_lora_rank, "seq": tokens,
                "family": "head_major", "score": "concat"}
        if self.q_lora_rank is not None:
            args["q_latent"] = self.q_lora_rank
        if self.yarn_factor is not None:
            args.update(yarn_factor=self.yarn_factor,
                        scale=self.softmax_scale)
        return args


def rope_interleaved(x: jax.Array, theta: float, table=None) -> jax.Array:
    """Rotary embedding of ``[B, T, H, D]`` at positions ``0 .. T-1``,
    pairs INTERLEAVED (``rope_interleave``): elements ``(x[2i],
    x[2i+1])`` rotated by ``t * theta^(-2i/D)``.  (The source
    de-interleaves q and k alike and rotates halves: the same scores.)
    ``table`` (:meth:`DeepseekV3Config.rope_table`): other frequencies
    than the plain ones, and a factor on cos and sin."""
    dim, seq = x.shape[-1], x.shape[1]
    if table is None:
        freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    else:
        freqs = table[0]
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if table is not None and table[1] != 1.0:
        cos, sin = table[1] * cos, table[1] * sin
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], dim // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _many(cfg) -> bool:
    return cfg.hc_mult > 1


def _names_parts(cfg, name: str):
    """Around the body of a part (``name``) whose module names its parts
    itself where the stream has several lanes (the connection's work is
    ``hc.coef`` and ``hc.mix``, the sub-layer's ``name``): there the
    part's scope; on one lane nothing, flax has put the module's name
    around all of it."""
    return step.scope(name) if _many(cfg) else contextlib.nullcontext()


class AttentionPart(nn.Module):
    """Latent attention ``F(N(u))`` and its residual (``hyper.residual``:
    ``x + F(N(x))`` on one lane).  A block names it ``attn``, and on one
    lane flax puts a module's name around its ops: that IS the step's
    part ``attn`` (``models/step.py``), split by ``step.ATTN_PIECES``;
    ``attn.mla`` is the kind of its kernels' call, ``mla.q_up`` and
    ``mla.kv_up`` plain names inside ``attn.proj``.  With several lanes
    the module names its parts itself (``step.names_its_parts``):
    ``hc.coef`` and ``hc.mix`` stand BESIDE ``attn``."""
    config: DeepseekV3Config
    names_its_parts = property(lambda self: _many(self.config))

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        heads, nope, rope = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
        rank, dim_v = cfg.kv_lora_rank, cfg.v_head_dim
        table = cfg.rope_table()

        # (on one lane flax's naming was never off: nothing changes)
        with step.named_children():
            res = hyper.residual(cfg, x)
            with _names_parts(cfg, "attn"):
                batch, seq = res.u.shape[:2]
                # the part's pieces (``step.ATTN_PIECES``); the kernels'
                # call names its own two inside its kind; ``mla.q_up``
                # and ``mla.kv_up`` are plain names inside ``attn.proj``
                with step.scope("attn.norm"):
                    h = RMSNorm(cfg.rms_eps, name="attn_norm")(res.u)
                if cfg.q_lora_rank is None:
                    with step.scope("attn.proj"):
                        q = _dense(cfg, heads * cfg.qk_head_dim, "wq",
                                   ("embed", "heads"))(h)
                else:
                    with step.scope("attn.proj"):
                        c = _dense(cfg, cfg.q_lora_rank, "wq_a",
                                   ("embed", None))(h)
                    with step.scope("attn.norm"):
                        c = _QueryNorm(cfg.rms_eps, name="q_norm")(c)
                    with step.scope("attn.proj"), \
                            jax.named_scope("mla.q_up"):
                        q = _dense(cfg, heads * cfg.qk_head_dim, "wq_b",
                                   (None, "heads"))(c)
                with step.scope("attn.proj"):
                    q = q.reshape(batch, seq, heads, cfg.qk_head_dim)
                    a = _dense(cfg, rank + rope, "wkv_a",
                               ("embed", None))(h)
                    latent = a[..., :rank]
                with step.scope("attn.norm"):
                    latent = _HeadNorm(cfg.rms_eps, name="kv_norm")(latent)
                with step.scope("attn.proj"):
                    k_rope = a[..., rank:].reshape(batch, seq, 1, rope)
                    with jax.named_scope("mla.kv_up"):
                        kv = _dense(cfg, heads * (nope + dim_v), "wkv_b",
                                    (None, "heads"))(latent).reshape(
                                        batch, seq, heads, nope + dim_v)
                with step.scope("attn.pos"):
                    q = jnp.concatenate(
                        [q[..., :nope],
                         rope_interleaved(q[..., nope:], cfg.rope_theta,
                                          table)], axis=-1)
                    k_rope = rope_interleaved(k_rope, cfg.rope_theta, table)
                with step.scope("attn.proj"):
                    k, v = kv[..., :nope], kv[..., nope:]
                with step.scope("attn.mla"):
                    attn = flash_attention(
                        q, k, v, k_rope=k_rope, causal=True,
                        scale=cfg.softmax_scale)
                with step.scope("attn.proj"):
                    attn = _dense(cfg, cfg.embed_dim, "wo",
                                  ("heads", "embed"))(
                        attn.reshape(batch, seq, heads * dim_v))
                with step.scope("attn.norm"):  # (one lane: the sum)
                    res.add(attn)
            return res.out()


class MLPPart(nn.Module):
    """``F(N(u))`` and its residual (``hyper.residual``): the dense
    SwiGLU of a leading layer, or the shared experts (one SwiGLU of their
    joint width) plus the routed experts held here.  Norm, SwiGLU and
    the sums are the step's part ``mlp``, the routed experts their own
    five BESIDE it, as ``afmoe.MLPPart``; with several lanes ``hc.coef``
    and ``hc.mix`` too."""
    config: DeepseekV3Config
    routed: bool
    names_its_parts = True

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        with step.named_children():
            res = hyper.residual(cfg, x)
            with step.scope("mlp"):
                h = RMSNorm(cfg.rms_eps, name="mlp_norm")(res.u)
                if self.routed:
                    shared = cfg.expert_dim * cfg.num_shared_experts
                    res.add(_swiglu(cfg, h, shared, "shared_"))
                else:
                    res.add(_swiglu(cfg, h, cfg.dense_dim, "w_"))
            if self.routed:
                routed = RoutedExperts(cfg, name="moe")(h, chosen)
                with step.scope("mlp"):
                    res.add(routed)
            return res.out()


class DeepseekV3Block(nn.Module):
    """One layer: its two parts, each over one sequence at a time and
    each recomputed on its own in the backward pass under ``remat``, as
    ``afmoe.AFMoEBlock``: but for a routed call's choices and row plan,
    which are kept from the forward (``step.remat``).  What a part's
    recompute starts from is the part's input: with ``hc_mult`` lanes,
    all of them."""
    config: DeepseekV3Config
    routed: bool   # an expert layer, or a leading dense one

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        attn, mlp = AttentionPart, MLPPart
        if cfg.remat == "full":
            attn, mlp = step.remat(attn), step.remat(mlp)
        return each_sequence((attn(cfg, name="attn"),
                              mlp(cfg, self.routed, name="mlp")), x, chosen)


class MultiToken(nn.Module):
    """DeepSeek-V3's multi-token module of depth 1 (arXiv:2412.19437,
    eq. 21-25): ``h'_t = W_M [N(h_t) ; N(E[token_{t+1}])]``, ``h'`` on
    every lane, ONE expert layer of its own, the lanes' sum, its own
    norm; the model's head reads the result for ``token_{t+2}``.  A
    model names it ``mtp``, which is the step's part of all it does.
    ``h [B, T, E]``: the stream before the final norm; ``nxt [B, T, E]``:
    the embedding of each position's NEXT token (the last position's is
    of no token: the loss leaves it out, and attention is causal)."""
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, h: jax.Array, nxt: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        both = jnp.concatenate(
            [RMSNorm(cfg.rms_eps, name="h_norm")(h),
             RMSNorm(cfg.rms_eps, name="e_norm")(nxt)], axis=-1)
        x = _dense(cfg, cfg.embed_dim, "proj", (None, "embed"))(both)
        x = hyper.lanes_of(x, cfg.hc_mult)
        x = DeepseekV3Block(cfg, True, name="h")(x, chosen)
        x = hyper.collapse(x, cfg.hc_mult)
        return RMSNorm(cfg.rms_eps, name="final_norm")(x).astype(
            jnp.float32)


class DeepseekV3(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def hidden(self, tokens: jax.Array,
               choices: Optional[List[jax.Array]] = None):
        """Final normed hidden states (float32) and the untied head
        ``[V, E]``, as ``afmoe.AFMoE.hidden`` (``choices``: a recorded
        routing to replay, a layer's ``[B*T, k]`` in order, the
        multi-token module's last).  With a multi-token module the
        hidden states are a PAIR: the model's and the module's."""
        cfg = self.config

        def table(name):
            return self.param(
                name, nn.with_partitioning(nn.initializers.normal(0.02),
                                           ("vocab", "embed")),
                (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)

        embed, head = table("embed"), table("head")
        with step.scope("embed"):
            x = embed.astype(cfg.dtype)[tokens]
            if _many(cfg):
                x = hyper.lanes_of(x, cfg.hc_mult)
        # the timeline says what was compiled: spans around the trace of
        # the layers (a call of a layer sees one sequence)
        seq = tokens.shape[1]
        with contextlib.ExitStack() as spans:
            spans.enter_context(telemetry.span(
                "model", "mla.plan", **cfg.plan_args(seq)))
            spans.enter_context(telemetry.span(
                "model", "moe.plan", **afmoe.routed_plan_args(cfg, seq)))
            if _many(cfg):
                spans.enter_context(telemetry.span(
                    "model", "hc.plan",
                    **hyper.plan_args(cfg, tokens.shape[0], seq)))
            for n in range(cfg.num_dense_layers + cfg.num_layers):
                i = n - cfg.num_dense_layers
                block = DeepseekV3Block(
                    cfg, i >= 0, name=f"h{i}" if i >= 0 else f"dense{n}")
                x = block(x) if choices is None or i < 0 \
                    else block(x, choices[i])
            if _many(cfg):
                with step.scope("hc.mix"):
                    x = hyper.collapse(x, cfg.hc_mult)
            mtp = None
            if cfg.num_mtp_layers:
                with step.scope("mtp"):   # the embedding of the NEXT token
                    nxt = embed.astype(cfg.dtype)[jnp.roll(tokens, -1, 1)]
                mtp = MultiToken(cfg, name="mtp")(
                    x, nxt, None if choices is None
                    else choices[cfg.num_layers])
        # the final norm is the head's: ``loss_fn`` opens the part again
        with step.scope("head"):
            x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
            x = x.astype(jnp.float32)
            return (x if mtp is None else (x, mtp)), head

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """The logits ``[B, T, V]`` (the model's own: no multi-token
        module's)."""
        x, head = self.hidden(tokens)
        if self.config.num_mtp_layers:
            x = x[0]
        return jnp.einsum("bte,ve->btv", x, head.astype(jnp.float32))

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: Optional[int] = None):
        seq = seq or self.config.max_seq_len
        tokens = jnp.zeros((batch, seq), jnp.int32)
        return self.init(rng, tokens)["params"]


def _routed_layers(cfg: DeepseekV3Config) -> List[Tuple[str, ...]]:
    """Where the routed layers stand in the tree, in the order of
    ``choices``: the expert layers', then the multi-token module's."""
    return [(f"h{i}",) for i in range(cfg.num_layers)] \
        + [("mtp", "h")] * cfg.num_mtp_layers


def _own_choices(model: nn.Module, state) -> List[jax.Array]:
    # sown once a call, and a call sees one sequence
    out = []
    for path in _routed_layers(model.config):
        at = state["intermediates"]
        for name in (*path, "mlp", "moe"):
            at = at[name]
        out.append(jnp.concatenate(at["expert_choice"]))
    return out


def loss_fn(model: nn.Module, params, tokens: jax.Array,
            head_chunk: int = 2048, head_logits_dtype: Any = None,
            choices: Optional[List[jax.Array]] = None,
            with_choices: bool = False):
    """Next-token cross entropy, ``afmoe.loss_fn`` (which it IS where the
    configuration has no multi-token module).  With one: ``L_main +
    MTP_WEIGHT x L_mtp``, ``L_mtp`` the mean cross entropy of the
    module's state at position ``t`` against ``token_{t+2}`` over the
    ``T - 2`` positions that have one, through the same chunked head."""
    cfg = model.config
    if not cfg.num_mtp_layers:
        return afmoe.loss_fn(model, params, tokens, head_chunk,
                             head_logits_dtype, choices, with_choices)
    from ray_tpu.ops.fused import chunked_lm_loss

    out = model.apply({"params": params}, tokens, choices,
                      method=type(model).hidden,
                      mutable=["intermediates"] if with_choices else False)
    ((x, mtp), head), state = out if with_choices else (out, None)
    kw = dict(chunk=head_chunk, logits_dtype=head_logits_dtype,
              compute_dtype=jnp.bfloat16 if cfg.dtype == jnp.bfloat16
              else None)
    # the scan's body inherits the name, and the gradient's two products
    # with it: they run in the forward's scan (``ops/fused.py``)
    with step.scope("head"):
        loss = chunked_lm_loss(x[:, :-1], head, tokens[:, 1:], **kw)
    with step.scope("mtp"):
        loss = loss + chunked_lm_loss(
            mtp[:, :-2], head, tokens[:, 2:], weight=MTP_WEIGHT, **kw)
    return (loss, _own_choices(model, state)) if with_choices else loss


def make_train_step(model: nn.Module, tx):
    """The donated ``(params, opt_state, tokens) -> (params, opt_state,
    loss)`` step, GPT-2's (``models/step.py``)."""
    return step.make_train_step(functools.partial(loss_fn, model), tx,
                                remat=model.config.remat)


@functools.partial(jax.jit, static_argnums=0)
def router_choices(model: nn.Module, params, tokens: jax.Array
                   ) -> List[jax.Array]:
    """``afmoe.router_choices``, the multi-token module's layer last."""
    _, state = model.apply({"params": params}, tokens,
                           method=type(model).hidden,
                           mutable=["intermediates"])
    return _own_choices(model, state)


@functools.partial(jax.jit, static_argnums=0)
def hc_stats(model: nn.Module, params, tokens: jax.Array
             ) -> Dict[str, jax.Array]:
    """What the hyper-connections made of a batch, a connection each
    (``[2 x layers]``: a layer's attention's, then its MLP's, in order;
    the multi-token module's left out): ``hyper.stats_of``'s three, the
    mean over the batch's sequences (the error their largest).  For a
    training loop to pass to :func:`report_hc_stats` and
    ``session.report``."""
    cfg = model.config
    _, state = model.apply({"params": params}, tokens,
                           method=type(model).hidden,
                           mutable=["intermediates"])
    layers = [f"dense{n}" for n in range(cfg.num_dense_layers)] \
        + [f"h{i}" for i in range(cfg.num_layers)]
    sown = [state["intermediates"][layer][part]["hc"]
            for layer in layers for part in ("attn", "mlp")]
    # sown once a call, and a call sees one sequence
    return {name: jnp.stack([fold(jnp.stack(c[name])) for c in sown])
            for name, fold in (("offdiag_mass", jnp.mean),
                               ("doubly_stochastic_error", jnp.max),
                               ("pre_entropy", jnp.mean))}


def report_hc_stats(stats: Dict[str, Any], model_name: str = "deepseek_v3"
                    ) -> Dict[str, float]:
    """Host side: the stats as the ``ray_tpu_hc_*`` gauges (tagged
    ``model_name`` and the connection's index), and as flat scalars for
    ``session.report``."""
    import numpy as np

    out: Dict[str, float] = {}
    for i, (mass, error, entropy) in enumerate(zip(*(
            np.asarray(stats[k]) for k in (
                "offdiag_mass", "doubly_stochastic_error", "pre_entropy")))):
        telemetry.hyper_connection(model_name, i, float(mass),
                                   float(error), float(entropy))
        out[f"hc/c{i}/offdiag_mass"] = float(mass)
        out[f"hc/c{i}/doubly_stochastic_error"] = float(error)
        out[f"hc/c{i}/pre_entropy"] = float(entropy)
    return out
