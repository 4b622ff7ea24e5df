"""Qwen3-Next hybrid decoder (``model_type`` ``qwen3_next``;
Qwen3-Next-80B-A3B-Instruct is the configuration the benchmark runs) in
flax linen, for the training path.

The stack is a PATTERN of layer kinds, one letter a layer: layer ``i``
(from 0) is ``F``, full attention, where ``(i + 1) %
full_attention_interval == 0``, else ``L``, linear attention.  Every
layer is two parts, ``x = x + mixer(norm(x))`` and ``x = x +
moe(norm(x))``, and every RMS norm but the gated one scales by ``1 + w``
(``w`` zero at initialisation: ``ops/fused.py`` ``fused_rmsnorm``'s
``offset``):

* ``L``, a Gated DeltaNet mixer (arXiv:2412.06464): ``[q | k | v], z``
  from one weight and ``b, a`` from a second (a product a part of their
  columns); a causal depthwise convolution of ``conv`` taps WITHOUT bias
  over ``[q | k | v]``, then ``silu`` (``ops/short_conv.py``); q and k
  l2-normalised per head, q scaled by ``d_k^-0.5``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; the gated
  delta rule's scan (``ops/gated_delta.py``: chunked, state and decays in
  float32), ``lin_key_heads`` key heads serving ``lin_value_heads`` value
  heads; a gated norm that normalises FIRST and gates after, ``w_n *
  rms(o) * silu(z)`` over each head's width; ``W_out``;
* ``F``, attention: ``[q | gate]`` from one weight, q and k RMS-normed
  per head, the FIRST ``rotary_dim`` elements of a head rotated
  (``partial_rotary_factor``), grouped heads inside the flash kernels,
  causal, the output times ``sigmoid(gate)`` before ``W_o``;
* the expert MLP of every layer: ``models/afmoe.py``'s
  :class:`RoutedExperts` (a SOFTMAX over all published experts in
  float32, the ``top_k`` largest, weights normalised over the chosen, a
  layer told which experts it holds, dropless grouped products) beside
  one shared SwiGLU expert behind a per-token gate ``sigmoid(w_s . h)``;
* untied embedding and head, a final norm; no auxiliary loss term, no
  multi-token module.

The layouts of the two joint projections are the program's own (the
source's checkpoint interleaves ``q, k, v, z`` by key head and ``q,
gate`` by head: a permutation of columns, no part of the mathematics).
Every part runs over one sequence of the batch at a time, recomputed on
its own under ``remat`` (a routed call's choices and row plan kept:
``models/step.py``).  The mixer's work stands under the step's five
``ssm.*`` parts, which name a recurrent mixer's shape of work whatever
its recurrence.
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
from ray_tpu.models.afmoe import (  # noqa: F401 — this model's step too
    RoutedExperts,
    _dense,
    _rope,
    _swiglu,
    each_sequence,
    loss_fn,
    make_train_step,
    router_choices,
    router_stats,
)
from ray_tpu.models.nemotron_h import _SplitDense
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.fused import _rmsnorm_ref, fused_rmsnorm
from ray_tpu.ops.gated_delta import gated_delta
from ray_tpu.ops.short_conv import short_conv

#: the ``ray_tpu_moe_*`` gauges under this model's name
report_router_stats = functools.partial(afmoe.report_router_stats,
                                        model_name="qwen3_next")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    #: the sequence as run (``max_position_embeddings``, 262144, only
    #: bounds it: RoPE needs no table)
    max_seq_len: int = 8192
    #: LINEAR layers.  With ``pattern`` None the stack is ``L^k F``: ``k``
    #: linear layers, then the one full-attention layer, so that depth 1
    #: already holds a layer of either kind
    num_layers: int = 36
    #: one letter a layer (``L`` | ``F``); None: ``L^num_layers F``; the
    #: published stack is ``published_pattern(48, 4)``
    pattern: Optional[str] = "LLLF" * 12
    embed_dim: int = 2048
    # -- full attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    #: elements of a head that are rotated, the first ones
    rotary_dim: int = 64
    rope_theta: float = 1e7
    # -- linear attention
    lin_key_heads: int = 16
    lin_value_heads: int = 32
    lin_key_dim: int = 128
    lin_value_dim: int = 128
    conv: int = 4
    chunk: int = 64
    # -- expert MLP
    expert_dim: int = 512
    shared_dim: int = 512
    #: the router's width: all published experts, held here or not
    num_experts: int = 512
    top_k: int = 10
    #: (first, count): the contiguous share of the experts held here
    experts_held: Tuple[int, int] = (0, 512)
    #: softmax over all experts, the chosen weights divided by their sum
    score_func: str = "softmax"
    route_scale: float = 1.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: scores, top-k and weights; float32 as the source's router
    router_dtype: Any = jnp.float32
    #: "" | "full": each part of a layer recomputed in the backward pass
    remat: str = ""

    def __post_init__(self):
        kinds = self.layer_kinds()
        if set(kinds) - set("LF") or kinds.count("L") != self.num_layers:
            raise ValueError(
                f"pattern {kinds!r} has to be letters L and F with "
                f"{self.num_layers} (num_layers) letters L")

    @classmethod
    def qwen3_next_80b_a3b(cls, **kw) -> "Qwen3NextConfig":
        return cls(**kw)   # 79.67B, about 3.9B a token

    @classmethod
    def qwen3_next_80b_a3b_share(cls, **kw) -> "Qwen3NextConfig":
        """One chip's share of sixteen (``benchmarks/configs/
        qwen3-next-80b-a3b.json``): published layers 0..3, ``LLLF``, 32
        of 512 experts, 18,992 of 151,936 vocabulary rows; every width
        as published; sequences of 4,096 (the harness's gradient check
        has no room at 8,192 beside the training state)."""
        defaults = dict(num_layers=3, pattern=None, experts_held=(0, 32),
                        vocab_size=18992, max_seq_len=4096)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "Qwen3NextConfig":  # for tests
        defaults = dict(vocab_size=256, max_seq_len=64, num_layers=2,
                        pattern=None, embed_dim=32, num_heads=4,
                        num_kv_heads=2, head_dim=16, rotary_dim=4,
                        lin_key_heads=2, lin_value_heads=4, lin_key_dim=8,
                        lin_value_dim=8, chunk=16, expert_dim=24,
                        shared_dim=24, num_experts=8, top_k=2,
                        experts_held=(0, 8))
        defaults.update(kw)
        return cls(**defaults)

    def layer_kinds(self) -> str:
        """One letter a layer as run."""
        return self.pattern if self.pattern is not None \
            else "L" * self.num_layers + "F"

    @property
    def num_expert_layers(self) -> int:
        """Every layer has its expert MLP (``decoder_sparse_step`` 1)."""
        return len(self.layer_kinds())

    @property
    def kv_heads(self) -> int:
        return math.gcd(self.num_heads, self.num_kv_heads)

    @property
    def key_dim(self) -> int:
        return self.lin_key_heads * self.lin_key_dim

    @property
    def value_dim(self) -> int:
        return self.lin_value_heads * self.lin_value_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: q, k and v."""
        return 2 * self.key_dim + self.value_dim

    def plan_args(self) -> Dict[str, Any]:
        """What stack was compiled, for the ``hybrid.plan`` span."""
        kinds = self.layer_kinds()
        return {"pattern": kinds, "mixers": kinds.count("L"),
                "experts": len(kinds), "attention": kinds.count("F"),
                "conv": self.conv, "norm_group": self.lin_value_dim,
                "gate_norm": "jnp", "scan": "gated_delta",
                "expert_form": "gated",
                "experts_held": self.experts_held[1]}


def published_pattern(layers: int, interval: int) -> str:
    """``F`` where ``(i + 1) % interval == 0``, else ``L``."""
    return "".join("F" if (i + 1) % interval == 0 else "L"
                   for i in range(layers))


class OffsetNorm(nn.Module):
    """RMS norm whose scale is ``1 + weight``, ``weight`` zero at
    initialisation (the source's zero-centred norms)."""
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.with_partitioning(
            nn.initializers.zeros, ("embed",)), (x.shape[-1],), jnp.float32)
        return fused_rmsnorm(x, w, eps=self.eps, offset=1.0)


class _OffsetHeadNorm(nn.Module):
    """The same over ``head_dim`` of ``[B, T, H, D]``, one weight for all
    heads (XLA fuses this one into its neighbours)."""
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.with_partitioning(
            nn.initializers.zeros, (None,)), (x.shape[-1],), jnp.float32)
        return _rmsnorm_ref(x, w, self.eps, 1.0)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4,
                                      16.0)).astype(dtype)


def l2_normalised(x: jax.Array, scale: float = 1.0) -> jax.Array:
    """``x / sqrt(sum x^2 + 1e-6) * scale`` over the last axis, the
    statistics in float32, the result in ``x``'s dtype."""
    f = x.astype(jnp.float32)
    return (f * (jax.lax.rsqrt(jnp.sum(f * f, -1, keepdims=True) + 1e-6)
                 * scale)).astype(x.dtype)


def norm_then_gate(o, z, scale, eps: float) -> jax.Array:
    """``scale * rms_norm(o) * silu(z)`` over the last axis of ``o z [..,
    H, D]``, one learned ``scale [D]`` shared by the heads: the norm
    FIRST, the gate after (``ops/gate_norm.py`` does the other order).
    Statistics, gate and scale in float32, the result in ``o``'s
    dtype."""
    f = o.astype(jnp.float32)
    f = f * jax.lax.rsqrt(jnp.mean(f * f, -1, keepdims=True) + eps)
    return (scale.astype(jnp.float32) * f
            * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


def partial_rope(x: jax.Array, rotary_dim: int, theta: float) -> jax.Array:
    """The FIRST ``rotary_dim`` elements of every head of ``[B, T, H,
    D]`` rotated (halves paired), the rest as they are."""
    return jnp.concatenate([_rope(x[..., :rotary_dim], theta),
                            x[..., rotary_dim:]], axis=-1)


def output_gated(attn: jax.Array, gate: jax.Array) -> jax.Array:
    """Attention's output ``[B, T, H D]`` times ``sigmoid`` of the gate
    its query projection's second half gave."""
    return attn * nn.sigmoid(gate)


def shared_weight(logit: jax.Array) -> jax.Array:
    """What the shared expert's result is weighed by, a token:
    ``sigmoid(w_s . h)`` of ``logit [B, T, 1]``."""
    return nn.sigmoid(logit)


class LinearPart(nn.Module):
    """``x + mixer(norm(x))``, a Gated DeltaNet mixer: five of the
    step's parts (``models/step.py``), and every op in one of them: the
    norm and the products are ``ssm.in_proj``'s; the l2 norms, ``beta``,
    the decays and the chunked scan ``ssm.scan``'s; the residual add
    ``ssm.out_proj``'s."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        f32 = jnp.float32
        batch, seq = x.shape[:2]
        hk, hv = cfg.lin_key_heads, cfg.lin_value_heads
        dk, dv = cfg.lin_key_dim, cfg.lin_value_dim

        def vector(name, init, shape, axes=(None,)):
            return self.param(name, nn.with_partitioning(init, axes), shape,
                              cfg.param_dtype)

        with step.scope("ssm.in_proj"):
            h = OffsetNorm(cfg.rms_eps, name="norm")(x)
            qkv, z = _SplitDense(cfg, (cfg.conv_dim, cfg.value_dim),
                                 name="in_proj_qkvz")(h)
            b, a = _SplitDense(cfg, (hv, hv), name="in_proj_ba")(h)
        with step.scope("ssm.conv"):
            qkv = short_conv(qkv, vector(
                "conv_kernel", nn.initializers.normal(0.02),
                (cfg.conv, cfg.conv_dim), (None, "mlp"))).astype(cfg.dtype)
            q, k, v = jnp.split(qkv, [cfg.key_dim, 2 * cfg.key_dim], axis=-1)
        with step.scope("ssm.scan"):
            q = l2_normalised(q.reshape(batch, seq, hk, dk), dk ** -0.5)
            k = l2_normalised(k.reshape(batch, seq, hk, dk))
            beta = jax.nn.sigmoid(b.astype(f32))
            g = -jnp.exp(vector("A_log", _a_log_init, (hv,)).astype(f32)) \
                * jax.nn.softplus(a.astype(f32) + vector(
                    "dt_bias", nn.initializers.ones, (hv,)).astype(f32))
            o = gated_delta(q, k, v.reshape(batch, seq, hv, dv), g, beta,
                            chunk=cfg.chunk)
        with step.scope("ssm.gate_norm"):
            scale = self.param("gate_norm", nn.with_partitioning(
                nn.initializers.ones, (None,)), (dv,), f32)
            y = norm_then_gate(o, z.reshape(batch, seq, hv, dv), scale,
                               cfg.rms_eps).astype(cfg.dtype)
        with step.scope("ssm.out_proj"):
            return x + _dense(cfg, cfg.embed_dim, "out_proj",
                              ("mlp", "embed"))(
                                  y.reshape(batch, seq, cfg.value_dim))


class AttentionPart(nn.Module):
    """``x + attention(norm(x))``: grouped heads, q and k normed per
    head, a partial rotation, the output gated.  A block names it
    ``attn``, and flax puts a module's name around its ops: that IS the
    step's part ``attn`` (``models/step.py``)."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from ray_tpu.parallel.mesh import get_global_mesh

        cfg = self.config
        batch, seq = x.shape[:2]
        heads, kv, dim = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        # the part's pieces (``step.ATTN_PIECES``); the kernels' call
        # names its own two inside its kind
        with step.scope("attn.norm"):
            h = OffsetNorm(cfg.rms_eps, name="attn_norm")(x)
        with step.scope("attn.proj"):
            q, gate = _SplitDense(cfg, (heads * dim, heads * dim),
                                  name="wq")(h)
            k = _dense(cfg, kv * dim, "wk", ("embed", "kv"))(h)
            v = _dense(cfg, kv * dim, "wv", ("embed", "kv"))(h)
        with step.scope("attn.norm"):  # (a view by heads moves nothing)
            q = _OffsetHeadNorm(cfg.rms_eps, name="q_norm")(
                q.reshape(batch, seq, heads, dim))
            k = _OffsetHeadNorm(cfg.rms_eps, name="k_norm")(
                k.reshape(batch, seq, kv, dim))
            v = v.reshape(batch, seq, kv, dim)
        with step.scope("attn.pos"):
            q = partial_rope(q, cfg.rotary_dim, cfg.rope_theta)
            k = partial_rope(k, cfg.rotary_dim, cfg.rope_theta)
        with step.scope("attn.full"):
            attn = flash_attention(q, k, v, causal=True,
                                   mesh=get_global_mesh())
        with step.scope("attn.gate"):
            attn = output_gated(attn.reshape(batch, seq, heads * dim), gate)
        with step.scope("attn.proj"):
            attn = _dense(cfg, cfg.embed_dim, "wo", ("heads", "embed"))(attn)
        with step.scope("attn.norm"):
            return x + attn


class ExpertPart(nn.Module):
    """``x + sigmoid(w_s . h) shared(h) + routed(h)``, ``h = norm(x)``:
    the gated shared expert plus the routed experts held here.  Norm,
    shared expert, its gate and the residual adds are the step's part
    ``mlp``, the routed experts their own five BESIDE it, as
    ``afmoe.MLPPart``."""
    config: Qwen3NextConfig
    names_its_parts = True

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        with step.named_children():
            with step.scope("mlp"):
                h = OffsetNorm(cfg.rms_eps, name="mlp_norm")(x)
                shared = _swiglu(cfg, h, cfg.shared_dim, "shared_")
                x = x + shared_weight(_dense(
                    cfg, 1, "shared_expert_gate", ("embed", None))(h)
                ) * shared
            routed = RoutedExperts(cfg, name="moe")(h, chosen)
            with step.scope("mlp"):
                return x + routed


#: a layer's letter -> (its mixer's part, the part's name in the tree,
#: the step's part its first op is of: ``models/step.py``)
MIXERS = {"L": (LinearPart, "mixer", "ssm.in_proj"),
          "F": (AttentionPart, "attn", "attn.norm")}


class HybridBlock(nn.Module):
    """One layer: its mixer and its expert MLP, each over one sequence
    at a time and each recomputed on its own in the backward pass under
    ``remat`` (``step.remat``: but for a routed call's choices and row
    plan, kept from the forward)."""
    config: Qwen3NextConfig
    kind: str      # "L" | "F"

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        mixer, name, first = MIXERS[self.kind]
        mlp = ExpertPart
        if self.config.remat == "full":
            mixer, mlp = step.remat(mixer), step.remat(mlp)
        return each_sequence((mixer(self.config, name=name),
                              mlp(self.config, name="mlp")), x, chosen,
                             (first, "mlp"))


class Qwen3Next(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def hidden(self, tokens: jax.Array,
               choices: Optional[List[jax.Array]] = None):
        """Final normed hidden states (float32) and the untied head
        ``[V, E]``, as ``afmoe.AFMoE.hidden`` (``choices``: a recorded
        routing to replay, a layer).  Layers are ``h<i>`` by their place
        in the stack as run, a linear layer's mixer ``mixer``, a full
        layer's ``attn``."""
        cfg = self.config

        def table(name):
            return self.param(
                name, nn.with_partitioning(nn.initializers.normal(0.02),
                                           ("vocab", "embed")),
                (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)

        embed, head = table("embed"), table("head")
        with step.scope("embed"):
            x = embed.astype(cfg.dtype)[tokens]
        seq = tokens.shape[1]
        # the timeline says what was compiled: spans around the trace of
        # the layers (a call of a layer sees one sequence)
        with telemetry.span("model", "hybrid.plan", **cfg.plan_args()), \
                telemetry.span("model", "moe.plan",
                               **afmoe.routed_plan_args(cfg, seq),
                               form="gated", router=cfg.score_func):
            for i, kind in enumerate(cfg.layer_kinds()):
                block = HybridBlock(cfg, kind, name=f"h{i}")
                x = block(x) if choices is None else block(x, choices[i])
        # the final norm is the head's: ``loss_fn`` opens the part again
        with step.scope("head"):
            x = OffsetNorm(cfg.rms_eps, name="final_norm")(x)
            return x.astype(jnp.float32), head

    def __call__(self, tokens: jax.Array) -> jax.Array:
        x, head = self.hidden(tokens)
        return jnp.einsum("bte,ve->btv", x, head.astype(jnp.float32))

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: Optional[int] = None):
        seq = seq or self.config.max_seq_len
        tokens = jnp.zeros((batch, seq), jnp.int32)
        return self.init(rng, tokens)["params"]
