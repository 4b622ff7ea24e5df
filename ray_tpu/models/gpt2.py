"""GPT-2 in flax linen, TPU-first.

The benchmark's dense model (``BENCHMARK.json``: GPT-2 large on one
chip, GPT-2 XL under FSDP on four).  Design notes:
- bfloat16 activations/params by default, float32 softmax/layernorm
  accumulation — MXU-friendly.
- attention goes through ``ray_tpu.ops.flash_attention`` (pallas kernel on
  TPU); sequence-parallel training swaps in ring attention via ``attn_impl``.
- every parameter is annotated with logical axes via
  ``nn.with_partitioning``, so ``ray_tpu.parallel.sharding`` presets map
  them onto the mesh without model changes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models import step
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.mesh import get_global_mesh
from ray_tpu.parallel.sharding import constrain_activation, fsdp_plan


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_ratio: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: "flash" | "ring" | "ulysses" | "reference"
    attn_impl: str = "flash"
    #: mesh axis name for ring/ulysses attention (sequence-parallel impls)
    sp_axis: str = "sp"
    #: activation rematerialization per block: "" (store activations),
    #: "full" (recompute everything in backward), or "dots" (save
    #: matmul outputs, recompute elementwise).  The benchmark's cells
    #: run "full": GPT-2 large's training state leaves no room for a
    #: step's activations on a 16 GB chip (``PERF.md`` section 4).
    remat: str = ""

    @classmethod
    def gpt2_small(cls, **kw) -> "GPT2Config":  # 124M
        return cls(num_layers=12, num_heads=12, embed_dim=768, **kw)

    @classmethod
    def gpt2_medium(cls, **kw) -> "GPT2Config":  # 350M
        return cls(num_layers=24, num_heads=16, embed_dim=1024, **kw)

    @classmethod
    def gpt2_large(cls, **kw) -> "GPT2Config":  # 774M
        return cls(num_layers=36, num_heads=20, embed_dim=1280, **kw)

    @classmethod
    def gpt2_xl(cls, **kw) -> "GPT2Config":  # 1.5B
        return cls(num_layers=48, num_heads=25, embed_dim=1600, **kw)

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":  # for tests
        defaults = dict(vocab_size=256, max_seq_len=128, num_layers=2,
                        num_heads=2, embed_dim=64)
        defaults.update(kw)
        return cls(**defaults)

    def num_params(self) -> int:
        e, v, l = self.embed_dim, self.vocab_size, self.num_layers
        per_layer = 12 * e * e + 13 * e  # qkv/proj/mlp + biases + lns
        return v * e + self.max_seq_len * e + l * per_layer + 2 * e

    def flops_per_token(self) -> float:
        """Training FLOPs per token, standard MFU convention (PaLM /
        nanoGPT): 6·N over ALL parameters + the attention term
        12·L·E·T.  With tied embeddings the single count of wte covers
        the LM-head matmul (the embedding lookup itself is a gather,
        not FLOPs — the two uses net out to one matmul's worth)."""
        attn = 12 * self.num_layers * self.embed_dim * self.max_seq_len
        return 6.0 * self.num_params() + attn


def _dense(features: int, config: GPT2Config, name: str,
           kernel_axes: tuple) -> nn.Dense:
    return nn.Dense(
        features,
        dtype=config.dtype,
        param_dtype=config.param_dtype,
        kernel_init=nn.with_partitioning(
            nn.initializers.normal(0.02), kernel_axes),
        bias_init=nn.with_partitioning(
            nn.initializers.zeros, (kernel_axes[-1],)),
        name=name,
    )


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        cfg = self.config
        head_dim = cfg.embed_dim // cfg.num_heads
        # the block's two halves are the parts ``attn`` and ``mlp``, each
        # with its norm, its projections and its residual add
        with step.scope("attn"):
            # under a mesh an activation lies on its batch shard, whole
            # along embed: a weight sharded there is gathered for its use
            # and its gradient scattered back.  Said inside the block, so
            # that the recomputed forward of a remat is laid out the same
            with step.scope("attn.norm"):
                x = constrain_activation(x, "batch", "seq", "embed")

                # block LNs emit cfg.dtype (statistics still accumulate
                # f32 inside flax): an f32 round-trip costs 3x the HBM
                # traffic
                h = nn.LayerNorm(dtype=cfg.dtype, name="ln_1",
                                 scale_init=nn.with_partitioning(
                                     nn.initializers.ones, ("embed",)),
                                 bias_init=nn.with_partitioning(
                                     nn.initializers.zeros, ("embed",)))(x)
            batch, seq = x.shape[:2]

            def heads(t):
                return t.reshape(batch, seq, cfg.num_heads, head_dim)

            with step.scope("attn.proj"):
                qkv = _dense(3 * cfg.embed_dim, cfg, "attn_qkv",
                             ("embed", "heads"))(h)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q, k, v = heads(q), heads(k), heads(v)
            # what attends names its own pieces (``attn.layout``,
            # ``attn.kernel``: ``ops/flash_attention.py``).  The ring
            # stands where the kernels would, its chunks' calls naming
            # theirs inside it; Ulysses' exchanges move data around such
            # a call (a reader takes the innermost piece)
            if cfg.attn_impl == "ring":
                from ray_tpu.parallel.ring_attention import ring_attention

                # under plain jit/GSPMD the sp axis is bound via the
                # global mesh (shard_map applied inside ring_attention);
                # inside a user shard_map the axis is already bound and
                # mesh is None
                with step.scope("attn.kernel"):
                    attn = ring_attention(
                        q, k, v, axis_name=cfg.sp_axis, causal=True,
                        mesh=get_global_mesh())
            elif cfg.attn_impl == "ulysses":
                from ray_tpu.parallel.ulysses import ulysses_attention

                # same binding rules as "ring": mesh when under plain
                # jit/GSPMD, already-bound axis inside a user shard_map
                with step.scope("attn.layout"):
                    attn = ulysses_attention(
                        q, k, v, axis_name=cfg.sp_axis, causal=True,
                        mesh=get_global_mesh())
            elif cfg.attn_impl == "reference":
                from ray_tpu.ops.flash_attention import _attention_reference

                attn = _attention_reference(q, k, v, True, head_dim ** -0.5)
            else:
                # same binding rule as "ring": under a multi-device mesh
                # the kernel runs per (batch, head) shard
                attn = flash_attention(q, k, v, causal=True,
                                       mesh=get_global_mesh())
            with step.scope("attn.proj"):
                attn = attn.reshape(batch, seq, cfg.embed_dim)
                attn = _dense(cfg.embed_dim, cfg, "attn_proj",
                              ("heads", "embed"))(attn)
            with step.scope("attn.norm"):
                x = x + attn

        with step.scope("mlp"):
            h = nn.LayerNorm(dtype=cfg.dtype, name="ln_2",
                             scale_init=nn.with_partitioning(
                                 nn.initializers.ones, ("embed",)),
                             bias_init=nn.with_partitioning(
                                 nn.initializers.zeros, ("embed",)))(x)
            h = _dense(cfg.mlp_ratio * cfg.embed_dim, cfg, "mlp_up",
                       ("embed", "mlp"))(h)
            h = nn.gelu(h)
            h = _dense(cfg.embed_dim, cfg, "mlp_down", ("mlp", "embed"))(h)
            if cfg.dropout > 0:
                h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
            return constrain_activation(x + h, "batch", "seq", "embed")


class GPT2(nn.Module):
    config: GPT2Config

    @nn.compact
    def hidden(self, tokens: jax.Array, deterministic: bool = True):
        """Final (post ln_f, f32) hidden states + the tied embedding —
        the training loss consumes these through the chunked LM head so
        full [B,T,V] logits are never materialized in HBM."""
        cfg = self.config
        wte = self.param(
            "wte",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("vocab", "embed")),
            (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)
        wpe = self.param(
            "wpe",
            nn.with_partitioning(nn.initializers.normal(0.01),
                                 (None, "embed")),
            (cfg.max_seq_len, cfg.embed_dim), cfg.param_dtype)
        seq = tokens.shape[1]
        with step.scope("embed"):
            # the lookup is a use of wte like any other: the rounded table
            # is gathered along embed for it, not the looked-up rows
            # exchanged
            table = constrain_activation(wte.astype(cfg.dtype), "vocab",
                                         "embed")
            x = table[tokens] + wpe.astype(cfg.dtype)[None, :seq]
            x = constrain_activation(x, "batch", "seq", "embed")
        block_cls = Block
        if cfg.remat == "full":
            block_cls = nn.remat(Block, static_argnums=(2,))
        elif cfg.remat == "dots":
            block_cls = nn.remat(
                Block, static_argnums=(2,),
                policy=jax.checkpoint_policies.dots_saveable)
        for i in range(cfg.num_layers):
            x = block_cls(cfg, name=f"h{i}")(x, deterministic)
        # the final norm is the head's: ``loss_fn`` opens the part again
        with step.scope("head"):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_f",
                             scale_init=nn.with_partitioning(
                                 nn.initializers.ones, ("embed",)),
                             bias_init=nn.with_partitioning(
                                 nn.initializers.zeros, ("embed",)))(x)
            return constrain_activation(x, "batch", "seq", "embed"), wte

    def __call__(self, tokens: jax.Array,
                 deterministic: bool = True) -> jax.Array:
        x, wte = self.hidden(tokens, deterministic)
        # tied embedding head (full logits — inference/eval path)
        return jnp.einsum("bte,ve->btv", x.astype(jnp.float32),
                          wte.astype(jnp.float32))

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: Optional[int] = None):
        seq = seq or self.config.max_seq_len
        tokens = jnp.zeros((batch, seq), jnp.int32)
        return self.init(rng, tokens)["params"]


def param_axes(config: GPT2Config):
    """The logical axes of every parameter, as a tree like the
    parameters'.  From an abstract init of ONE layer on a few tokens (no
    kernel is traced); layer 0 stands for every layer."""
    one = GPT2(dataclasses.replace(config, num_layers=1, remat="",
                                   attn_impl="reference"))
    boxed = jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0), seq=8))
    axes = jax.tree.map(lambda b: tuple(b.names), boxed,
                        is_leaf=lambda b: hasattr(b, "names"))
    layer = axes.pop("h0")
    return {**axes, **{f"h{i}": layer for i in range(config.num_layers)}}


def loss_fn(model: GPT2, params, tokens: jax.Array,
            head_chunk: int = 8192) -> jax.Array:
    """Next-token cross entropy (labels = tokens shifted left).

    The LM head + softmax run in token chunks (``chunked_lm_loss``):
    full [B,T,V] f32 logits would be the single largest HBM tensor *and*
    the dominant bandwidth consumer at small model sizes (2 x 6 GiB at
    batch 32 — the profile that motivated this).  A chunk's logits are
    made once: under a gradient the same scan step takes ``d hidden``
    and ``d wte`` from them (``ops/fused.py`` ``weighted_token_loss``),
    and the backward pass has no head left to run."""
    from ray_tpu.ops.fused import chunked_lm_loss

    x, wte = model.apply({"params": params}, tokens, method=GPT2.hidden)
    # bf16-activation models run the head matmuls on the MXU in bf16;
    # logits accumulate and are stored in f32
    compute = jnp.bfloat16 if model.config.dtype == jnp.bfloat16 else None
    # the scan's body inherits the name, the gradient's products with it
    with step.scope("head"):
        return chunked_lm_loss(x[:, :-1], wte, tokens[:, 1:],
                               chunk=head_chunk, compute_dtype=compute,
                               mesh=get_global_mesh())


def make_train_step(model: GPT2, tx):
    """The jitted train step the GPT-2 entry points share: chunked-head
    loss, gradients, one ``tx`` (optax) update (``models/step.py``, the
    step of every model here)."""
    remat = model.config.remat
    return step.make_train_step(
        functools.partial(loss_fn, model), tx, remat=remat,
        # under a mesh the timeline says what the step asks of it
        plan=lambda params: fsdp_plan(
            params, functools.partial(param_axes, model.config),
            passes=3 if remat == "full" else 2))
