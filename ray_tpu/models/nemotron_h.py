"""Nemotron-H hybrid decoder (``model_type`` ``nemotron_h``; NVIDIA's
Nemotron-3-Nano-30B-A3B is the configuration the benchmark runs) in flax
linen, for the training path.

The stack is a PATTERN of layer kinds (``hybrid_override_pattern``), one
letter a layer, and every layer is ONE part behind its own RMS norm and
residual, ``x = x + part(norm(x))``:

* ``M``, a Mamba-2 mixer: ``[z | u | dt_raw] = W_in h`` (one weight, a
  product a part: :class:`_SplitDense`); a causal
  depthwise convolution of ``conv`` taps with bias over ``u``, then
  ``silu``; ``u`` split into ``xs [T, H, P]``, ``B`` and ``C [T, G, N]``;
  ``dt = softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)``; the selective
  state-space scan (``ray_tpu.ops.ssd``: chunked, Pallas kernels forward
  and backward, state and decays in float32); ``y * silu(z)``, then an RMS
  norm in groups of ``H P / G`` (gate first, norm after, as the source's
  ``norm_before_gate=False``); ``W_out``;
* ``*``, attention: grouped heads (``num_kv_heads`` K/V heads serve
  ``num_heads`` query heads inside the flash kernels), causal, and NO
  positional rotation (the mixers carry order);
* ``E``, an expert MLP: the routed layer IS ``models/afmoe.py``'s
  (:class:`RoutedExperts`: sigmoid scores in float32 over all published
  experts, the ``top_k`` largest, weights normalised over the chosen and
  scaled by ``route_scale``, a layer told which experts it holds,
  dropless grouped products) with experts of TWO matrices,
  ``down(relu(up h)^2)``, beside one shared expert of the same form;
* untied embedding and head, a final RMS norm; no auxiliary loss term.

The source's selection bias (``e_score_correction_bias``, updated
outside the gradient, zero at initialisation) is left out, as in
``afmoe.py``.  Every layer runs its part over one sequence of the batch
at a time, recomputed on its own under ``remat`` (a routed call's
choices and row plan kept: ``models/step.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
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
    each_sequence,
    loss_fn,
    make_train_step,
    router_choices,
    router_stats,
)
from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops import gate_norm
from ray_tpu.ops.short_conv import short_conv
from ray_tpu.ops.ssd import ssd

#: the ``ray_tpu_moe_*`` gauges under this model's name
report_router_stats = functools.partial(afmoe.report_router_stats,
                                        model_name="nemotron_h")

#: ``hybrid_override_pattern`` of Nemotron-3-Nano-30B-A3B: 23 mixers, 23
#: expert MLPs, 6 attention layers
NANO_30B_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    #: the sequence as run (``max_position_embeddings``, 262144, only
    #: bounds it: nothing here has a table of positions)
    max_seq_len: int = 8192
    #: EXPERT layers.  With ``pattern`` None the stack is ``(EM)^k *``:
    #: ``k`` pairs of an expert layer and a mixer, then one attention
    #: layer, so that depth 1 already holds a layer of every kind
    num_layers: int = 23
    #: one letter a layer (``M`` | ``E`` | ``*``), as published; holds
    #: ``num_layers`` letters ``E``
    pattern: Optional[str] = NANO_30B_PATTERN
    embed_dim: int = 2688
    # -- attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # -- mixer
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv: int = 4
    chunk: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # -- expert MLP
    expert_dim: int = 1856
    shared_dim: int = 3712
    #: the router's width: all published experts, held here or not
    num_experts: int = 128
    top_k: int = 6
    #: (first, count): the contiguous share of the experts held here
    experts_held: Tuple[int, int] = (0, 128)
    route_scale: float = 2.5
    #: two matrices an expert, ``down(relu(up h)^2)``
    expert_form: str = "relu2"
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: scores, top-k and weights; float32 as the source's router
    router_dtype: Any = jnp.float32
    #: "" | "full": each layer's part recomputed in the backward pass
    remat: str = ""

    def __post_init__(self):
        kinds = self.layer_kinds()
        if set(kinds) - set("ME*") or kinds.count("E") != self.num_layers:
            raise ValueError(
                f"pattern {kinds!r} has to be letters M, E and * with "
                f"{self.num_layers} (num_layers) letters E")

    @classmethod
    def nemotron_3_nano_30b_a3b(cls, **kw) -> "NemotronHConfig":
        return cls(**kw)   # 31.6B, about 3.2B active

    @classmethod
    def nemotron_3_nano_30b_a3b_share(cls, **kw) -> "NemotronHConfig":
        """One chip's share of sixteen (``benchmarks/configs/
        nemotron-3-nano-30b-a3b.json``): published layers 34..42,
        ``EMEMEMEM*``, 8 of 128 experts, 16,384 of 131,072 vocabulary
        rows; every width as published."""
        defaults = dict(num_layers=4, pattern=None, experts_held=(0, 8),
                        vocab_size=16384, max_seq_len=8192)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":  # for tests
        defaults = dict(vocab_size=256, max_seq_len=64, num_layers=2,
                        pattern=None, embed_dim=32, num_heads=4,
                        num_kv_heads=2, head_dim=16, ssm_heads=8,
                        ssm_head_dim=8, ssm_groups=2, ssm_state=16,
                        chunk=16, expert_dim=24, shared_dim=48,
                        num_experts=8, top_k=2, experts_held=(0, 8))
        defaults.update(kw)
        return cls(**defaults)

    def layer_kinds(self) -> str:
        """One letter a layer as run."""
        return self.pattern if self.pattern is not None \
            else "EM" * self.num_layers + "*"

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``xs``, ``B`` and ``C``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def kv_heads(self) -> int:
        return math.gcd(self.num_heads, self.num_kv_heads)

    def plan_args(self) -> Dict[str, Any]:
        """What stack was compiled, for the ``hybrid.plan`` span
        (``gate_norm``: the form a sequence's shapes chose)."""
        kinds = self.layer_kinds()
        tile = gate_norm.tiles(self.max_seq_len, self.ssm_inner,
                               self.ssm_groups, self.dtype)
        return {"pattern": kinds, "mixers": kinds.count("M"),
                "experts": kinds.count("E"), "attention": kinds.count("*"),
                "conv": self.conv, "norm_group":
                self.ssm_inner // self.ssm_groups,
                "gate_norm": "kernel" if tile else "jnp",
                "expert_form": self.expert_form,
                "experts_held": self.experts_held[1]}


def _relu2(cfg, h, width: int, prefix: str):
    up = _dense(cfg, width, prefix + "up", ("embed", "mlp"))(h)
    return _dense(cfg, cfg.embed_dim, prefix + "down",
                  ("mlp", "embed"))(jnp.square(nn.relu(up)))


def _dt_bias_init(cfg: NemotronHConfig):
    """The inverse softplus of a step drawn log-uniform in
    ``[time_step_min, time_step_max]``, floored at ``time_step_floor``."""
    def init(key, shape, dtype):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), cfg.time_step_floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def causal_conv(u: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """``silu(bias + sum_j w[j] * u[t - (taps - 1) + j])`` over ``u [B, T,
    C]``, ``u`` zero before the sequence; ``w [taps, C]``.  The sum and
    ``silu`` in float32, the result in ``u``'s dtype: one pass over the
    channels where the shapes are the kernels' (``ops/short_conv.py``)."""
    return short_conv(u, w, bias)


def gated_group_norm(y, z, scale, groups: int, eps: float) -> jax.Array:
    """``RMSNorm(y * silu(z))`` in ``groups`` groups of the last axis,
    one learned scale over all of it.  Gate, statistics and scale in
    float32, the result in ``y``'s dtype: one pass over the rows where
    the shapes are the kernels' (``ops/gate_norm.py``)."""
    return gate_norm.gate_norm(y, z, scale, groups, eps)


class MixerPart(nn.Module):
    """``x + mixer(norm(x))``, a Mamba-2 mixer: five of the step's parts
    (``models/step.py``), and every op in one of them: the norm and the
    three products are ``ssm.in_proj``'s, the step sizes and decays the
    scan's, the residual add ``ssm.out_proj``'s."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        batch, seq = x.shape[:2]
        heads, dim = cfg.ssm_heads, cfg.ssm_head_dim
        groups, state, inner = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_inner

        def vector(name, init, shape, axes=(None,)):
            return self.param(name, nn.with_partitioning(init, axes), shape,
                              cfg.param_dtype)

        with step.scope("ssm.in_proj"):
            h = RMSNorm(cfg.rms_eps, name="norm")(x)
            z, u, dt_raw = _SplitDense(cfg, (inner, cfg.conv_dim, heads),
                                       name="in_proj")(h)
        with step.scope("ssm.conv"):
            c = causal_conv(
                u, vector("conv_kernel", nn.initializers.normal(0.02),
                          (cfg.conv, cfg.conv_dim), (None, "mlp")),
                vector("conv_bias", nn.initializers.zeros, (cfg.conv_dim,),
                       ("mlp",))).astype(cfg.dtype)
            xs, b, c = jnp.split(c, [inner, inner + groups * state], axis=-1)
        with step.scope("ssm.scan"):
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + vector(
                "dt_bias", _dt_bias_init(cfg), (heads,)).astype(jnp.float32))
            a = -jnp.exp(vector("A_log", _a_log_init, (heads,)).astype(
                jnp.float32))
            skip = vector("D", nn.initializers.ones, (heads,))
            y = ssd(xs.reshape(batch, seq, heads, dim), dt, a,
                    b.reshape(batch, seq, groups, state),
                    c.reshape(batch, seq, groups, state),
                    skip.astype(jnp.float32), chunk=cfg.chunk)
        with step.scope("ssm.gate_norm"):
            scale = _GateScale(name="gate_norm")(inner)
            g = gated_group_norm(y.reshape(batch, seq, inner), z, scale,
                                 groups, cfg.rms_eps).astype(cfg.dtype)
        with step.scope("ssm.out_proj"):
            return x + _dense(cfg, cfg.embed_dim, "out_proj",
                              ("mlp", "embed"))(g)


class _SplitDense(nn.Module):
    """``in_proj``: one weight ``kernel [embed, sum(widths)]`` as
    ``nn.Dense`` holds it, one product a part of its columns.  The gate,
    the convolution's input and the step sizes then leave as arrays of
    their own: a kernel's operand has to, and cut out of ONE result each
    is a copy of its bytes held beside the whole (PERF.md, PR 49)."""
    config: NemotronHConfig
    widths: Tuple[int, ...]

    @nn.compact
    def __call__(self, h: jax.Array) -> List[jax.Array]:
        cfg = self.config
        kernel = self.param("kernel", nn.with_partitioning(
            nn.initializers.normal(0.02), ("embed", "mlp")),
            (h.shape[-1], sum(self.widths)), cfg.param_dtype)
        kernel = kernel.astype(cfg.dtype)
        # slices, not ``jnp.split``: under that one's transpose the
        # compiler keeps a copy of every mixer's weight and both its
        # moments for the whole step (+0.86 GiB in the Nemotron cell)
        starts = itertools.accumulate(self.widths[:-1], initial=0)
        return [h @ kernel[:, start:start + width]
                for start, width in zip(starts, self.widths)]


class _GateScale(nn.Module):
    """The gated norm's learned scale (``gate_norm/scale``)."""

    @nn.compact
    def __call__(self, width: int):
        return self.param("scale", nn.with_partitioning(
            nn.initializers.ones, ("mlp",)), (width,), jnp.float32)


class AttentionPart(nn.Module):
    """``x + attention(norm(x))``: grouped heads, causal, no rotation.
    A block names it ``attn``, and flax puts a module's name around its
    ops: that IS the step's part ``attn`` (``models/step.py``)."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from ray_tpu.parallel.mesh import get_global_mesh

        cfg = self.config
        batch, seq = x.shape[:2]
        heads, kv, dim = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        # the part's pieces (``step.ATTN_PIECES``); the kernels' call
        # names its own two inside its kind
        with step.scope("attn.norm"):
            h = RMSNorm(cfg.rms_eps, name="attn_norm")(x)
        with step.scope("attn.proj"):
            q = _dense(cfg, heads * dim, "wq", ("embed", "heads"))(h)
            k = _dense(cfg, kv * dim, "wk", ("embed", "kv"))(h)
            v = _dense(cfg, kv * dim, "wv", ("embed", "kv"))(h)
            q = q.reshape(batch, seq, heads, dim)
            k = k.reshape(batch, seq, kv, dim)
            v = v.reshape(batch, seq, kv, dim)
        with step.scope("attn.full"):
            attn = flash_attention(q, k, v, causal=True,
                                   mesh=get_global_mesh())
        with step.scope("attn.proj"):
            attn = _dense(cfg, cfg.embed_dim, "wo", ("heads", "embed"))(
                attn.reshape(batch, seq, heads * dim))
        with step.scope("attn.norm"):
            return x + attn


class ExpertPart(nn.Module):
    """``x + shared(norm(x)) + routed(norm(x))``: the shared expert plus
    the routed experts held here, all ``down(relu(up h)^2)``.  Norm,
    shared expert and residual adds are the step's part ``mlp``, the
    routed experts their own five BESIDE it, as ``afmoe.MLPPart``."""
    config: NemotronHConfig
    names_its_parts = True

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        with step.named_children():
            with step.scope("mlp"):
                h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
                x = x + _relu2(cfg, h, cfg.shared_dim, "shared_")
            routed = RoutedExperts(cfg, name="moe")(h, chosen)
            with step.scope("mlp"):
                return x + routed


#: a layer's letter -> (its part, the part's name in the tree, the
#: layer's: ``h<i>`` expert layers, ``m<i>`` mixers, ``a<i>`` attention,
#: the step's parts its first and its last op are of: ``models/step.py``)
PARTS = {"M": (MixerPart, "mixer", "m", ("ssm.in_proj", "ssm.out_proj")),
         "E": (ExpertPart, "mlp", "h", ("mlp", "mlp")),
         "*": (AttentionPart, "attn", "a", ("attn.norm", "attn.norm"))}


class HybridBlock(nn.Module):
    """One layer: its one part, over one sequence at a time and
    recomputed on its own in the backward pass under ``remat``: but for
    a routed call's choices and row plan, which are kept from the
    forward (``step.remat``; a mixer or an attention layer names
    nothing, so nothing of it is kept)."""
    config: NemotronHConfig
    kind: str      # "M" | "E" | "*"

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        part, name, _, around = PARTS[self.kind]
        if self.config.remat == "full":
            part = step.remat(part)
        return each_sequence((part(self.config, name=name),), x, chosen,
                             around)


class NemotronH(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def hidden(self, tokens: jax.Array,
               choices: Optional[List[jax.Array]] = None):
        """Final normed hidden states (float32) and the untied head
        ``[V, E]``, as ``afmoe.AFMoE.hidden`` (``choices``: a recorded
        routing to replay).  Layers are named by kind and count: expert
        layers ``h<i>``, mixers ``m<i>``, attention ``a<i>``."""
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
        count = dict.fromkeys(PARTS, 0)
        # the timeline says what was compiled: spans around the trace of
        # the layers (a call of a layer sees one sequence)
        with telemetry.span("model", "hybrid.plan", **cfg.plan_args()), \
                telemetry.span("model", "moe.plan",
                               **afmoe.routed_plan_args(cfg, seq),
                               form=cfg.expert_form):
            for kind in cfg.layer_kinds():
                i, count[kind] = count[kind], count[kind] + 1
                block = HybridBlock(cfg, kind, name=f"{PARTS[kind][2]}{i}")
                x = block(x, choices[i]) if kind == "E" and \
                    choices is not None else block(x)
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
