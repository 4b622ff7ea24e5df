"""Sparse Mixture-of-Experts transformer (Mixtral/GShard-style), TPU-first.

Net-new vs the reference (SURVEY.md §2.5: expert parallelism is absent
there); the design follows public GShard/Switch practice: top-k softmax
routing with a FIXED expert capacity so every tensor shape is static
under jit, dispatch/combine as one-hot einsums (MXU-friendly — no
scatters), experts evaluated as one stacked ``vmap`` over an
"expert"-annotated parameter stack so the ``ep`` mesh axis shards them
via GSPMD (``ray_tpu.parallel.sharding.EP_RULES``) and XLA emits the
token all-to-alls over ICI.

Aux load-balancing loss (Switch Transformer eq. 4) is sown under
``intermediates/aux_loss`` and summed by :func:`loss_fn`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.gpt2 import GPT2Config, _dense
from ray_tpu.ops.flash_attention import flash_attention


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    max_seq_len: int = 1024
    num_layers: int = 8
    num_heads: int = 8
    embed_dim: int = 512
    mlp_ratio: int = 4
    num_experts: int = 8
    top_k: int = 2
    #: buffer slots per expert = capacity_factor * tokens * top_k / E
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "flash"

    @classmethod
    def tiny(cls, **kw) -> "MoEConfig":
        defaults = dict(vocab_size=256, max_seq_len=128, num_layers=2,
                        num_heads=2, embed_dim=64, num_experts=4, top_k=2)
        defaults.update(kw)
        return cls(**defaults)

    def num_params(self) -> int:
        e = self.embed_dim
        m = self.mlp_ratio * e
        per_layer = 4 * e * e + self.num_experts * (2 * e * m) \
            + e * self.num_experts
        return self.vocab_size * e + self.max_seq_len * e \
            + self.num_layers * per_layer

    def active_params_per_token(self) -> int:
        """Parameters touched per token (top-k experts, not all)."""
        e = self.embed_dim
        m = self.mlp_ratio * e
        per_layer = 4 * e * e + self.top_k * (2 * e * m)
        return self.vocab_size * e + self.num_layers * per_layer


class SparseMoEMLP(nn.Module):
    """Top-k routed expert MLP with static capacity.

    Dispatch: tokens [G, E_dim] -> expert buffers [E, C, E_dim] via a
    one-hot combine tensor (einsum, no dynamic shapes); experts are a
    single stacked parameter ([E, ...], logical axis "expert") applied
    with vmap, so sharding "expert" -> ep runs each expert's matmuls on
    its owning devices and GSPMD inserts the all-to-alls.
    """

    config: MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        B, T, D = x.shape
        G = B * T  # token group routed together
        E, K = cfg.num_experts, cfg.top_k
        C = max(1, int(cfg.capacity_factor * G * K / E))
        tokens = x.reshape(G, D)

        # --- router (f32 for numerics, per Switch recommendations)
        router_logits = _dense(E, _as_gpt2(cfg), "router",
                               ("embed", "expert"))(
            tokens.astype(jnp.float32))
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)  # [G, K]
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        # --- aux load-balancing loss (Switch eq. 4)
        density = jnp.mean(
            jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
        density_proxy = jnp.mean(probs, axis=0)
        aux = jnp.sum(density * density_proxy) * E * cfg.router_aux_coef
        self.sow("intermediates", "aux_loss", aux)

        # --- capacity assignment: position of each (token, k) within its
        # expert's buffer; overflowing tokens drop (standard GShard)
        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # [G,K,E]
        flat = onehot.reshape(G * K, E)
        pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(G, K, E)
        within = (pos_in_expert < C) & (onehot == 1)
        # dispatch tensor [G, K, E, C]
        pos_oh = jax.nn.one_hot(
            jnp.sum(pos_in_expert * onehot, axis=-1), C,
            dtype=x.dtype)  # [G, K, C]
        dispatch = (within.astype(x.dtype)[..., None]
                    * onehot.astype(x.dtype)[..., None]
                    * pos_oh[:, :, None, :])  # [G,K,E,C]
        combine = dispatch * gate_vals.astype(x.dtype)[:, :, None, None]

        # --- expert buffers [E, C, D]
        expert_in = jnp.einsum("gkec,gd->ecd",
                               dispatch, tokens.astype(cfg.dtype))

        # --- stacked experts, vmapped; params carry the "expert" axis
        up = self.param(
            "up",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("expert", "embed", "mlp")),
            (E, D, cfg.mlp_ratio * D), cfg.param_dtype)
        down = self.param(
            "down",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("expert", "mlp", "embed")),
            (E, cfg.mlp_ratio * D, D), cfg.param_dtype)

        def expert_fwd(buf, w_up, w_down):
            h = jnp.einsum("cd,dm->cm", buf, w_up.astype(cfg.dtype))
            h = nn.gelu(h)
            return jnp.einsum("cm,md->cd", h, w_down.astype(cfg.dtype))

        expert_out = jax.vmap(expert_fwd)(expert_in, up, down)  # [E,C,D]

        # --- combine back to token order
        out = jnp.einsum("gkec,ecd->gd", combine, expert_out)
        return out.reshape(B, T, D)


def _as_gpt2(cfg: MoEConfig) -> GPT2Config:
    """Adapter so gpt2._dense's partitioned initializers are reusable."""
    return GPT2Config(vocab_size=cfg.vocab_size,
                      max_seq_len=cfg.max_seq_len,
                      num_layers=cfg.num_layers, num_heads=cfg.num_heads,
                      embed_dim=cfg.embed_dim, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype)


class MoEBlock(nn.Module):
    config: MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 deterministic: bool = True) -> jax.Array:
        cfg = self.config
        g = _as_gpt2(cfg)
        head_dim = cfg.embed_dim // cfg.num_heads
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x)
        qkv = _dense(3 * cfg.embed_dim, g, "attn_qkv",
                     ("embed", "heads"))(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        B, T = x.shape[:2]

        def heads(t):
            return t.reshape(B, T, cfg.num_heads, head_dim)

        if cfg.attn_impl == "reference":
            from ray_tpu.ops.flash_attention import _attention_reference

            attn = _attention_reference(heads(q), heads(k), heads(v),
                                        True, head_dim ** -0.5)
        else:
            from ray_tpu.parallel.mesh import get_global_mesh

            # under a multi-device mesh the kernel runs per shard
            attn = flash_attention(heads(q), heads(k), heads(v),
                                   causal=True, mesh=get_global_mesh())
        attn = attn.reshape(B, T, cfg.embed_dim)
        x = x + _dense(cfg.embed_dim, g, "attn_proj",
                       ("heads", "embed"))(attn)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x)
        return x + SparseMoEMLP(cfg, name="moe")(h)


class MoETransformer(nn.Module):
    """Decoder-only sparse-MoE LM with tied embeddings."""

    config: MoEConfig

    @nn.compact
    def hidden(self, tokens: jax.Array, deterministic: bool = True):
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
        x = wte.astype(cfg.dtype)[tokens] + \
            wpe.astype(cfg.dtype)[None, :seq]
        for i in range(cfg.num_layers):
            x = MoEBlock(cfg, name=f"h{i}")(x, deterministic)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        return x, wte

    def __call__(self, tokens: jax.Array,
                 deterministic: bool = True) -> jax.Array:
        x, wte = self.hidden(tokens, deterministic)
        return jnp.einsum("bte,ve->btv", x.astype(jnp.float32),
                          wte.astype(jnp.float32))

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: Optional[int] = None):
        seq = seq or self.config.max_seq_len
        tokens = jnp.zeros((batch, seq), jnp.int32)
        return self.init(rng, tokens)["params"]


def loss_fn(model: MoETransformer, params, tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy + router aux loss."""
    from ray_tpu.ops.fused import chunked_lm_loss

    (x, wte), state = model.apply(
        {"params": params}, tokens, method=MoETransformer.hidden,
        mutable=["intermediates"])
    compute = jnp.bfloat16 if model.config.dtype == jnp.bfloat16 else None
    lm = chunked_lm_loss(x[:, :-1].astype(jnp.float32),
                         wte.astype(jnp.float32), tokens[:, 1:],
                         compute_dtype=compute)
    aux_leaves = jax.tree_util.tree_leaves(
        state.get("intermediates", {}))
    aux = sum(jnp.sum(a) for a in aux_leaves) if aux_leaves else 0.0
    return lm + aux
