"""DeepSeek-V3-style decoder (``model_type`` ``deepseek_v3``; kakaocorp's
Kanana-2-30B-A3B is the configuration the benchmark runs) in flax linen,
for the training path.

What the block is, as the public ``config.json`` keys fix it (what they
leave open is listed under ``assumed`` in ``benchmarks/configs/
kanana-2-30b-a3b.json``):

* latent attention (MLA) without a query latent (``q_lora_rank`` null):
  ``q = W_q h`` is ``nope + rope`` wide a head; ``W_kva h`` gives a
  latent of ``kv_lora_rank`` (RMS-normalised, its own learned scale) and
  ONE rotary key head of ``rope`` that every query head shares; ``W_kvb``
  lifts the latent to each head's own key part (``nope``) and value
  (``v_head_dim``).  RoPE, pairs interleaved, on q's rotary part and on
  the shared key only.  Scores scaled by ``(nope + rope) ** -0.5``,
  causal, no window.  The flash kernels take the key in its two parts
  (``flash_attention(k_rope=)``): nothing is broadcast or padded in HBM;
* pre-norm only: ``x + attn(norm(x))``, then ``x + mlp(norm(x))``;
* leading dense layers with a SwiGLU MLP, then expert layers: the routed
  layer IS ``models/afmoe.py``'s (:class:`RoutedExperts`: sigmoid scores
  in float32 over all published experts, the ``top_k`` largest, weights
  normalised over the chosen and scaled by ``route_scale``, a layer told
  which experts it holds, dropless grouped products) at this model's
  numbers, beside the shared experts as one SwiGLU of their joint width;
* untied embedding and head, a final RMS norm; no auxiliary loss term.

The source's selection bias (``e_score_correction_bias``, updated
outside the gradient, zero at initialisation) is left out, as in
``afmoe.py``.  Every layer runs its two parts over one sequence of the
batch at a time, each recomputed on its own under ``remat`` (a routed
call's choices and row plan kept: ``models/step.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.core import telemetry
from ray_tpu.models import afmoe, step
from ray_tpu.models.afmoe import (  # noqa: F401 — this model's step too
    RoutedExperts,
    _dense,
    _HeadNorm,
    _swiglu,
    each_sequence,
    loss_fn,
    make_train_step,
    router_choices,
    router_stats,
)
from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops.flash_attention import flash_attention

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

    def plan_args(self, tokens: int) -> Dict[str, Any]:
        """What attention was compiled, for the ``mla.plan`` span: the
        widths, and which kernels carry them (the head-major family,
        since a ``nope + rope``-wide head fills no whole 128-lane slabs;
        ``concat``: a tile's two key parts joined along lanes, so one
        ``nope + rope``-deep score product)."""
        return {"heads": self.num_heads, "nope": self.qk_nope_dim,
                "rope": self.qk_rope_dim, "value": self.v_head_dim,
                "latent": self.kv_lora_rank, "seq": tokens,
                "family": "head_major", "score": "concat"}


def rope_interleaved(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of ``[B, T, H, D]`` at positions ``0 .. T-1``,
    pairs INTERLEAVED (``rope_interleave``): elements ``(x[2i],
    x[2i+1])`` rotated by ``t * theta^(-2i/D)``.  (The source
    de-interleaves q and k alike and rotates halves: the same scores.)"""
    dim, seq = x.shape[-1], x.shape[1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], dim // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


class AttentionPart(nn.Module):
    """``x + attention(norm(x))``, latent attention.  A block names it
    ``attn``, and flax puts a module's name around its ops: that IS the
    step's part ``attn`` (``models/step.py``); ``mla.kv_up`` and
    ``attn.mla`` are pieces of it."""
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        batch, seq = x.shape[:2]
        heads, nope, rope = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
        rank, dim_v = cfg.kv_lora_rank, cfg.v_head_dim

        h = RMSNorm(cfg.rms_eps, name="attn_norm")(x)
        q = _dense(cfg, heads * cfg.qk_head_dim, "wq", ("embed", "heads"))(
            h).reshape(batch, seq, heads, cfg.qk_head_dim)
        a = _dense(cfg, rank + rope, "wkv_a", ("embed", None))(h)
        latent = _HeadNorm(cfg.rms_eps, name="kv_norm")(a[..., :rank])
        k_rope = a[..., rank:].reshape(batch, seq, 1, rope)
        with jax.named_scope("mla.kv_up"):
            kv = _dense(cfg, heads * (nope + dim_v), "wkv_b",
                        (None, "heads"))(latent).reshape(
                            batch, seq, heads, nope + dim_v)
        q = jnp.concatenate(
            [q[..., :nope], rope_interleaved(q[..., nope:], cfg.rope_theta)],
            axis=-1)
        k_rope = rope_interleaved(k_rope, cfg.rope_theta)
        with step.scope("attn.mla"):
            attn = flash_attention(q, kv[..., :nope], kv[..., nope:],
                                   k_rope=k_rope, causal=True)
        attn = attn.reshape(batch, seq, heads * dim_v)
        return x + _dense(cfg, cfg.embed_dim, "wo", ("heads", "embed"))(attn)


class MLPPart(nn.Module):
    """``x + mlp(norm(x))``: the dense SwiGLU of a leading layer, or the
    shared experts (one SwiGLU of their joint width) plus the routed
    experts held here.  Norm, SwiGLU and residual adds are the step's
    part ``mlp``, the routed experts their own five BESIDE it, as
    ``afmoe.MLPPart``."""
    config: DeepseekV3Config
    routed: bool
    names_its_parts = True

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        with step.named_children():
            with step.scope("mlp"):
                h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
                if not self.routed:
                    return x + _swiglu(cfg, h, cfg.dense_dim, "w_")
                shared = cfg.expert_dim * cfg.num_shared_experts
                x = x + _swiglu(cfg, h, shared, "shared_")
            routed = RoutedExperts(cfg, name="moe")(h, chosen)
            with step.scope("mlp"):
                return x + routed


class DeepseekV3Block(nn.Module):
    """One layer: its two parts, each over one sequence at a time and
    each recomputed on its own in the backward pass under ``remat``, as
    ``afmoe.AFMoEBlock``: but for a routed call's choices and row plan,
    which are kept from the forward (``step.remat``)."""
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


class DeepseekV3(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def hidden(self, tokens: jax.Array,
               choices: Optional[List[jax.Array]] = None):
        """Final normed hidden states (float32) and the untied head
        ``[V, E]``, as ``afmoe.AFMoE.hidden`` (``choices``: a recorded
        routing to replay)."""
        cfg = self.config

        def table(name):
            return self.param(
                name, nn.with_partitioning(nn.initializers.normal(0.02),
                                           ("vocab", "embed")),
                (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)

        embed, head = table("embed"), table("head")
        with step.scope("embed"):
            x = embed.astype(cfg.dtype)[tokens]
        # the timeline says what was compiled: spans around the trace of
        # the layers (a call of a layer sees one sequence)
        seq = tokens.shape[1]
        with telemetry.span("model", "mla.plan", **cfg.plan_args(seq)), \
                telemetry.span("model", "moe.plan",
                               **afmoe.routed_plan_args(cfg, seq)):
            for n in range(cfg.num_dense_layers + cfg.num_layers):
                i = n - cfg.num_dense_layers
                block = DeepseekV3Block(
                    cfg, i >= 0, name=f"h{i}" if i >= 0 else f"dense{n}")
                x = block(x) if choices is None or i < 0 \
                    else block(x, choices[i])
        # the final norm is the head's: ``loss_fn`` opens the part again
        with step.scope("head"):
            x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
            return x.astype(jnp.float32), head

    def __call__(self, tokens: jax.Array) -> jax.Array:
        x, head = self.hidden(tokens)
        return jnp.einsum("bte,ve->btv", x, head.astype(jnp.float32))

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: Optional[int] = None):
        seq = seq or self.config.max_seq_len
        tokens = jnp.zeros((batch, seq), jnp.int32)
        return self.init(rng, tokens)["params"]
