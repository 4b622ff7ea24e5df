"""Llama-family decoder (RMSNorm, SwiGLU, RoPE, GQA) in flax linen.

Serving/inference flagship (BASELINE.json config: Llama-7B inference
replicas).  Same logical-axis annotation scheme as GPT-2; KV heads can be
fewer than Q heads (grouped-query attention), KV cache support for
autoregressive decoding.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention, _attention_reference
from ray_tpu.ops.fused import fused_rmsnorm


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    embed_dim: int = 4096
    mlp_dim: int = 11008
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama2_13b(cls, **kw) -> "LlamaConfig":
        return cls(num_layers=40, num_heads=40, embed_dim=5120,
                   mlp_dim=13824, **kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        defaults = dict(vocab_size=256, max_seq_len=128, num_layers=2,
                        num_heads=4, num_kv_heads=2, embed_dim=64,
                        mlp_dim=128)
        defaults.update(kw)
        return cls(**defaults)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding. x: [B, T, H, D]."""
    dim = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        w = self.param("scale",
                       nn.with_partitioning(nn.initializers.ones,
                                            ("embed",)),
                       (x.shape[-1],), jnp.float32)
        return fused_rmsnorm(x, w, eps=self.eps)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None):
        cfg = self.config
        head_dim = cfg.embed_dim // cfg.num_heads
        batch, seq = x.shape[:2]

        def dense(feat, name, axes):
            return nn.Dense(feat, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype,
                            kernel_init=nn.with_partitioning(
                                nn.initializers.normal(0.02), axes),
                            name=name)

        h = RMSNorm(cfg.rms_eps, name="attn_norm")(x)
        q = dense(cfg.num_heads * head_dim, "wq", ("embed", "heads"))(h)
        k = dense(cfg.num_kv_heads * head_dim, "wk", ("embed", "kv"))(h)
        v = dense(cfg.num_kv_heads * head_dim, "wv", ("embed", "kv"))(h)
        q = q.reshape(batch, seq, cfg.num_heads, head_dim)
        k = k.reshape(batch, seq, cfg.num_kv_heads, head_dim)
        v = v.reshape(batch, seq, cfg.num_kv_heads, head_dim)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)

        new_cache = None
        if kv_cache is not None:
            k_cache, v_cache, cache_len = kv_cache
            k = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, cache_len, 0, 0))
            v = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, cache_len, 0, 0))
            new_cache = (k, v, cache_len + seq)

        if kv_cache is not None:
            # decode path: mask positions beyond cache_len + seq; the
            # cached K/V heads are copied out to the query heads here
            repeat = cfg.num_heads // cfg.num_kv_heads
            if repeat > 1:
                k = jnp.repeat(k, repeat, axis=2)
                v = jnp.repeat(v, repeat, axis=2)
            attn = _decode_attention(q, k, v, positions, head_dim)
        else:
            from ray_tpu.parallel.mesh import get_global_mesh

            # grouped heads inside the kernel: query head h reads K/V
            # head h // group, no copies of K and V
            attn = flash_attention(q, k, v, causal=True,
                                   mesh=get_global_mesh())
        attn = attn.reshape(batch, seq, cfg.num_heads * head_dim)
        x = x + dense(cfg.embed_dim, "wo", ("heads", "embed"))(attn)

        h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
        gate = dense(cfg.mlp_dim, "w_gate", ("embed", "mlp"))(h)
        up = dense(cfg.mlp_dim, "w_up", ("embed", "mlp"))(h)
        h = nn.silu(gate) * up
        x = x + dense(cfg.embed_dim, "w_down", ("mlp", "embed"))(h)
        return (x, new_cache) if kv_cache is not None else (x, None)


def _decode_attention(q, k, v, positions, head_dim):
    """Attention against a (padded) KV cache: key t visible iff its
    position <= the query's position (cache slots are position-indexed)."""
    scale = head_dim ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    t_kv = k.shape[1]
    kv_pos = jnp.arange(t_kv)[None, None, None, :]
    q_pos = positions[:, None, :, None]
    s = jnp.where(kv_pos <= q_pos, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


class Llama(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 kv_caches=None):
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None], tokens.shape)
        emb = self.param(
            "embedding",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("vocab", "embed")),
            (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)
        x = emb.astype(cfg.dtype)[tokens]
        new_caches = []
        for i in range(cfg.num_layers):
            cache = kv_caches[i] if kv_caches is not None else None
            x, new_cache = LlamaBlock(cfg, name=f"layer{i}")(
                x, positions, cache)
            new_caches.append(new_cache)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        logits = jnp.einsum("bte,ve->btv", x.astype(jnp.float32),
                            emb.astype(jnp.float32))
        if kv_caches is not None:
            return logits, new_caches
        return logits

    def init_kv_caches(self, batch: int, max_len: int):
        cfg = self.config
        head_dim = cfg.embed_dim // cfg.num_heads
        shape = (batch, max_len, cfg.num_kv_heads, head_dim)
        return [(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype), 0)
                for _ in range(cfg.num_layers)]
