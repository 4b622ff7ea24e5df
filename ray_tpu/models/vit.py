"""Vision Transformer, TPU-first flax implementation.

Image-family coverage next to ResNet (the reference's vision models live
in its framework integrations; here ViT is first-class).  TPU notes:
patchify is one conv (MXU), encoder blocks reuse the pallas flash
attention (non-causal), parameters carry logical axes ("embed", "heads",
"mlp", "vocab"→classes) so every ``ray_tpu.parallel.sharding`` preset
applies unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "flash"

    @classmethod
    def base(cls, **kw) -> "ViTConfig":  # ViT-B/16
        return cls(**kw)

    @classmethod
    def large(cls, **kw) -> "ViTConfig":  # ViT-L/16
        return cls(num_layers=24, num_heads=16, embed_dim=1024, **kw)

    @classmethod
    def tiny(cls, **kw) -> "ViTConfig":  # for tests
        defaults = dict(image_size=32, patch_size=8, num_classes=10,
                        num_layers=2, num_heads=2, embed_dim=64)
        defaults.update(kw)
        return cls(**defaults)

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2


def _dense(features: int, cfg: ViTConfig, name: str, kernel_axes: tuple
           ) -> nn.Dense:
    return nn.Dense(
        features, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        kernel_init=nn.with_partitioning(
            nn.initializers.normal(0.02), kernel_axes),
        bias_init=nn.with_partitioning(
            nn.initializers.zeros, (kernel_axes[-1],)),
        name=name)


class EncoderBlock(nn.Module):
    config: ViTConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        head_dim = cfg.embed_dim // cfg.num_heads
        B, T, _ = x.shape
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x)
        qkv = _dense(3 * cfg.embed_dim, cfg, "attn_qkv",
                     ("embed", "heads"))(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, T, cfg.num_heads, head_dim)

        if cfg.attn_impl == "reference":
            from ray_tpu.ops.flash_attention import _attention_reference

            attn = _attention_reference(heads(q), heads(k), heads(v),
                                        False, head_dim ** -0.5)
        else:
            from ray_tpu.parallel.mesh import get_global_mesh

            # under a multi-device mesh the kernel runs per shard
            attn = flash_attention(heads(q), heads(k), heads(v),
                                   causal=False, mesh=get_global_mesh())
        attn = attn.reshape(B, T, cfg.embed_dim)
        x = x + _dense(cfg.embed_dim, cfg, "attn_proj",
                       ("heads", "embed"))(attn)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x)
        h = _dense(cfg.mlp_ratio * cfg.embed_dim, cfg, "mlp_up",
                   ("embed", "mlp"))(h)
        h = nn.gelu(h)
        return x + _dense(cfg.embed_dim, cfg, "mlp_down",
                          ("mlp", "embed"))(h)


class ViT(nn.Module):
    config: ViTConfig

    @nn.compact
    def __call__(self, images: jax.Array) -> jax.Array:
        """images [B, H, W, C] -> class logits [B, num_classes]."""
        cfg = self.config
        x = nn.Conv(
            cfg.embed_dim,
            kernel_size=(cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_partitioning(
                nn.initializers.normal(0.02),
                (None, None, None, "embed")),
            name="patch_embed")(images.astype(cfg.dtype))
        B = x.shape[0]
        x = x.reshape(B, -1, cfg.embed_dim)  # [B, patches, D]
        cls_tok = self.param(
            "cls", nn.with_partitioning(nn.initializers.zeros,
                                        (None, None, "embed")),
            (1, 1, cfg.embed_dim), cfg.param_dtype)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls_tok.astype(cfg.dtype),
                              (B, 1, cfg.embed_dim)), x], axis=1)
        pos = self.param(
            "pos_embed",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 (None, None, "embed")),
            (1, cfg.num_patches + 1, cfg.embed_dim), cfg.param_dtype)
        x = x + pos.astype(cfg.dtype)
        for i in range(cfg.num_layers):
            x = EncoderBlock(cfg, name=f"h{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x[:, 0])
        return _dense(cfg.num_classes, cfg, "head",
                      ("embed", "vocab"))(x).astype(jnp.float32)

    def init_params(self, rng: jax.Array, batch: int = 1):
        cfg = self.config
        images = jnp.zeros(
            (batch, cfg.image_size, cfg.image_size, 3), jnp.float32)
        return self.init(rng, images)["params"]


def loss_fn(model: ViT, params, images: jax.Array,
            labels: jax.Array) -> jax.Array:
    logits = model.apply({"params": params}, images)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, labels[:, None], axis=-1))
