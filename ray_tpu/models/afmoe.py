"""AFMoE decoder (arcee-ai Trinity family, ``model_type`` ``afmoe``) in
flax linen, for the training path.

What the block is (the public ``config.json`` keys fix the sizes; what
they leave open is set by the family's convention and listed under
``assumed`` in ``benchmarks/configs/trinity-mini.json``):

* attention: grouped heads (``num_kv_heads`` K/V heads serve
  ``num_heads`` query heads, inside the flash kernels, no copies), RMS
  norm of q and k per head, RoPE on q and k in ``sliding_attention``
  layers (window ``window``) and none in ``full_attention`` layers, the
  output gated by ``sigmoid(W_g h)`` before ``W_o``;
* every sub-layer between two RMS norms (``x + post(f(pre(x)))``);
* leading dense layers with a SwiGLU MLP, then expert layers: a
  sigmoid router over ALL published experts, the ``top_k`` largest, their
  scores normalised over the chosen and scaled by ``route_scale``, one
  shared SwiGLU expert for every token;
* untied embedding and head, the embedding scaled by ``sqrt(embed_dim)``
  (muP).

**The layer is told which experts it holds** (``experts_held = (first,
count)``, any contiguous share): it routes over all of them, computes
its own experts' part for the tokens routed to them, adds the shared
expert, and leaves out what absent experts would have added — nothing
stands in for other chips.  Dispatch drops nothing at any imbalance:
``ray_tpu.ops.grouped_matmul`` sorts the landed (token, choice) pairs by
expert into a buffer sized for the worst case (every choice of every
token lands here) and runs one grouped product per projection over the
row tiles that are live, so the products' cost follows the rows that
landed.  The ``expert`` logical axis stays on the parameters, so the same
layer takes an ``ep`` mesh axis later; on one chip it runs without its
exchange.

Every layer runs its two parts over one sequence of the batch at a time
(a kernel call a sequence, the activation memory of one sequence).
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
from ray_tpu.models import step
from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.fused import _rmsnorm_ref
from ray_tpu.parallel import expert

#: rows of one tile of the grouped products
BLOCK_ROWS = 256


@dataclasses.dataclass(frozen=True)
class AFMoEConfig:
    vocab_size: int = 200192
    #: the sequence as run (the source's ``max_position_embeddings``,
    #: 131072, only bounds it: RoPE needs no table)
    max_seq_len: int = 8192
    #: EXPERT layers; the leading dense layers are counted apart
    num_layers: int = 30
    num_dense_layers: int = 2
    #: published index of the first expert layer as run: a cut model
    #: skips to a whole period of the sliding/full pattern
    expert_layer_start: Optional[int] = None
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    embed_dim: int = 2048
    dense_dim: int = 6144
    expert_dim: int = 1024
    #: the router's width: all published experts, held here or not
    num_experts: int = 128
    top_k: int = 8
    #: (first, count): the contiguous share of the experts held here
    experts_held: Tuple[int, int] = (0, 128)
    route_scale: float = 2.826
    window: int = 2048
    #: every n-th layer (published index + 1 divisible by n) is full
    global_every: int = 4
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    mup: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: scores, top-k and weights; float32 as the source's router
    router_dtype: Any = jnp.float32
    #: "" | "full": each part of a layer recomputed in the backward
    #: pass, but for a routed call's decisions (``step.remat``)
    remat: str = ""

    @classmethod
    def trinity_mini(cls, **kw) -> "AFMoEConfig":  # 26B, about 3B active
        return cls(**kw)

    @classmethod
    def trinity_mini_share(cls, **kw) -> "AFMoEConfig":
        """One chip's share of eight (``benchmarks/configs/
        trinity-mini.json``): one dense layer and the whole period of
        expert layers 4..7 (sliding, sliding, sliding, full), 16 of 128
        experts, 25,024 of 200,192 vocabulary rows; every width as
        published."""
        defaults = dict(num_layers=4, num_dense_layers=1,
                        expert_layer_start=4, experts_held=(0, 16),
                        vocab_size=25024, max_seq_len=8192)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "AFMoEConfig":  # for tests
        defaults = dict(vocab_size=256, max_seq_len=64, num_layers=2,
                        num_dense_layers=1, expert_layer_start=2,
                        num_heads=4, num_kv_heads=2, head_dim=16,
                        embed_dim=32, dense_dim=64, expert_dim=32,
                        num_experts=8, top_k=2, experts_held=(0, 8),
                        window=24)
        defaults.update(kw)
        return cls(**defaults)

    @property
    def kv_heads(self) -> int:
        """K/V heads as run: the published count where it divides the
        query heads (a test that cuts the heads keeps a valid group)."""
        return math.gcd(self.num_heads, self.num_kv_heads)

    def layer_kinds(self) -> List[str]:
        """``sliding`` or ``full`` for every layer as run, dense layers
        first, by the published index of each."""
        start = self.num_dense_layers if self.expert_layer_start is None \
            else self.expert_layer_start
        index = list(range(self.num_dense_layers)) + [
            start + i for i in range(self.num_layers)]
        return ["full" if (j + 1) % self.global_every == 0 else "sliding"
                for j in index]

    def plan_args(self, tokens: int) -> Dict[str, Any]:
        """What was compiled, for the ``moe.plan`` span."""
        return {**routed_plan_args(self, tokens), "window": self.window,
                "heads": self.num_heads, "kv_heads": self.kv_heads,
                "layers": ",".join(k[0] for k in self.layer_kinds())}


def routed_plan_args(cfg, tokens: int) -> Dict[str, Any]:
    """The routed layer's part of a ``moe.plan`` span, for any
    configuration that carries one (:class:`RoutedExperts`' fields).
    ``buffer_passes``: passes of XLA a layer-call makes over ALL rows of
    the worst-case buffer whatever the load (the rest is work of the
    live tiles); ``row_gather``: how ``rows <- tokens`` keeps to the
    live rows: ``reach``, one gather of the least of ``gather_reaches``
    of the buffer that holds them.  The sums ``tokens <- rows`` on a
    TPU: ``walk_tile`` tokens a step of the kernel that walks the
    ``pairs`` of a call and reads a row for those that landed alone (0:
    no tile fits these shapes, a row is gathered for every pair);
    ``walked``: what it walks, the ``table`` ``[k, tile]`` of the pairs'
    rows as the plan has it (no list of the landed pairs is made).
    ``product_tiles``: the grouped products' weight blocks and their
    sweeps over the rows, ``product_vmem_bytes`` the most VMEM a kernel
    of theirs is given, both from the function the kernels take their
    tiles from (``gm.product_tiles``).  ``kept``: what a recompute of
    the call replays and does not make again (``step.KEPT``: the
    router's choices and the row plan, made once, in the forward);
    ``kept_bytes``: their bytes a call, from the shapes."""
    idx = jax.ShapeDtypeStruct((tokens, cfg.top_k), jnp.int32)
    plan = jax.eval_shape(lambda i: _tables(gm.plan_rows(
        i, 0, cfg.experts_held[1], block_m=BLOCK_ROWS)), idx)
    return {"experts": cfg.num_experts, "held_first": cfg.experts_held[0],
            "held": cfg.experts_held[1], "top_k": cfg.top_k,
            "row_bound": tokens * cfg.top_k, "block_rows": BLOCK_ROWS,
            "buffer_passes": 0, "row_gather": "reach",
            "gather_reaches": ",".join(f"1/{r}" for r in gm.REACHES),
            "walk_tile": gm.walk_tile(tokens, cfg.embed_dim) or 0,
            "pairs": tokens * cfg.top_k, "walked": "table",
            "kept": ",".join(step.KEPT),
            "kept_bytes": sum(a.size * a.dtype.itemsize
                              for a in (idx, *plan.values())),
            **gm.product_tiles(BLOCK_ROWS, cfg.embed_dim, cfg.expert_dim,
                               jnp.dtype(cfg.dtype).itemsize)}


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of ``[B, T, H, D]`` at positions ``0 .. T-1``:
    pairs ``(x[i], x[i + D/2])`` rotated by ``t * theta^(-2i/D)``."""
    dim, seq = x.shape[-1], x.shape[1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


class _HeadNorm(nn.Module):
    """RMS norm over ``head_dim`` of ``[B, T, H, D]``, one learned scale
    for all heads (the fused kernel takes rows of the model's width; XLA
    fuses this one into its neighbours)."""
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.with_partitioning(
            nn.initializers.ones, (None,)), (x.shape[-1],), jnp.float32)
        return _rmsnorm_ref(x, w, self.eps)


def _dense(cfg, features: int, name: str, axes: tuple):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.with_partitioning(
                        nn.initializers.normal(0.02), axes), name=name)


def _swiglu(cfg, h, width: int, prefix: str):
    gate = _dense(cfg, width, prefix + "gate", ("embed", "mlp"))(h)
    up = _dense(cfg, width, prefix + "up", ("embed", "mlp"))(h)
    return _dense(cfg, cfg.embed_dim, prefix + "down",
                  ("mlp", "embed"))(nn.silu(gate) * up)


#: a router's scores of its logits, by the configuration's ``score_func``
_SCORES = {"sigmoid": jax.nn.sigmoid,
           "softmax": functools.partial(jax.nn.softmax, axis=-1)}


def route(cfg, h: jax.Array, w_router: jax.Array,
          chosen: Optional[jax.Array] = None):
    """Scores (float32 by default) over all published experts: sigmoid,
    or where the configuration's ``score_func`` says so a softmax over
    all of them; the ``top_k`` largest, weights normalised over the
    chosen and scaled.
    ``h [T, E]`` -> ``(expert ids [T, k], weights [T, k] f32, the
    router's own choice [T, k])``.  ``chosen [T, k]``: a recorded routing
    to replay, in place of the router's own largest (the weights are
    still its scores').

    The weights are the scores AT the ids, whoever chose them, and the
    router's own ids are a decision the call keeps (``step.keep``): a
    recompute (``step.remat``) reads the forward's ids and weighs them
    by the recomputed scores, so its ``top_k`` is dead and a weight can
    lie on no other expert's row than the kept plan's."""
    logits = jnp.dot(h.astype(cfg.router_dtype),
                     w_router.astype(cfg.router_dtype),
                     precision=jax.lax.Precision.HIGHEST)
    scores = _SCORES[getattr(cfg, "score_func", "sigmoid")](logits)
    own = jax.lax.top_k(scores, cfg.top_k)[1]
    # the kept copy stays in here; ``own`` goes out as it came
    idx = step.keep("choices", own) if chosen is None else chosen
    top = _scores_at(scores, idx).astype(jnp.float32)
    return idx, cfg.route_scale * top / top.sum(-1, keepdims=True), own


def _scores_at(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """``scores [T, E]`` at ``idx [T, k]`` (a token's ids distinct):
    ``take_along_axis`` in every bit, forward and backward, as a select
    against the ids and a sum over the experts, one nonzero term a
    choice.  A gather of ``[T, k]`` entries and its scatter backward are
    serial work on a TPU (0.47 ms a call at Trinity's 8,192 x 8, forward
    and recomputed: 7.5 ms a step, PERF.md section 6, PR 53); this is a
    fused pass over ``[T, k, E]`` (compiled for a described v5e,
    Nemotron's step holds 29 MB more with it than with the gather and
    Mellum's peaks 0.75 GiB lower).  The
    barrier keeps it apart from the sum over the chosen that follows: a
    TPU fuses the two into one reduce, adds a token's weights in another
    order, and the last bit of a quarter of them moves
    (``tests/test_chip_compile.py`` holds the text compiled for a v5e to
    two fusions)."""
    at = idx[:, :, None] == jnp.arange(scores.shape[1], dtype=idx.dtype)
    return jax.lax.optimization_barrier(
        jnp.where(at, scores[:, None, :], 0).sum(-1))


def _tables(plan) -> Dict[str, jax.Array]:
    """The arrays of a ``gm.RowPlan`` that dispatch, the products and
    combine read, forward and backward; ``sizes`` and ``fits`` are
    reported, not read."""
    return {f: getattr(plan, f) for f in (
        "row_pair", "row_valid", "pair_row", "pair_valid", "tile_expert",
        "n_live")}


def _kept(plan):
    """``plan`` (``gm.plan_rows``) with the tables that are read stamped
    as a decision the call keeps (``step.keep``): what reads THIS copy
    under a recompute (``step.remat``) reads the forward's tables, and
    no plan is made again.  The caller's ``plan`` stays as it came, for
    what leaves the call (``sizes``, ``n_live``): a kept value that left
    a ``shard_map`` body unread would fail the trace (jax 0.9.0,
    ``partial_eval``: its residual is a ``DropVar``).  Called beside
    ``plan_rows`` and outside the ``moe.plan`` scope: what takes a kept
    table's shape again in the backward pass is no plan made again, and
    stands under no part."""
    return plan._replace(**step.keep("plan", _tables(plan)))


def _landed_sums(cfg, rows: jax.Array, weights: jax.Array, matrices,
                 plan) -> jax.Array:
    """``rows [T, E]`` with their ``weights [T, k]`` -> ``[T, E]``, every
    token's weighted sum over the experts of ``plan`` (``gm.plan_rows``:
    the held ones its pairs landed on): dispatch, the grouped products,
    combine, each the step's part of its name.  On one chip that is the
    layer's result; on a chip of a group, its part of it."""
    with step.scope("moe.dispatch"):
        rows = gm.dispatch(rows, plan)
    with step.scope("moe.experts"):
        out = gm.expert_products(rows, matrices, plan)
    with step.scope("moe.combine"):
        return gm.combine(out, weights, plan, dtype=cfg.dtype)


class RoutedExperts(nn.Module):
    """The routed part of an expert layer, for the share held here.
    ONE layer for every model that routes so (``models/deepseek_v3.py``
    too): ``config`` is any dataclass with ``num_experts``, ``top_k``,
    ``experts_held``, ``route_scale``, ``expert_dim``, ``dtype``,
    ``param_dtype`` and ``router_dtype``.  An expert is the gated three
    matrices, ``down(silu(gate h) * up h)``, unless the configuration's
    ``expert_form`` says ``"relu2"``: two, ``down(relu(up h)^2)``
    (``models/nemotron_h.py``).

    A configuration with ``expert_axis`` (a mesh axis), under a global
    mesh with several chips along it, makes this ONE CHIP OF THE GROUP
    that shares the layer: ``experts_held`` is then the group's share,
    the parameters lie over the axis by expert, ``h``'s batch is split
    over it, and the layer runs with its exchange
    (:meth:`_exchanged`).

    A call DECIDES once: the router's own choices ``[T, k]``
    (:func:`route`) and the row plan made from them (:func:`_kept`,
    beside ``plan_rows``) are stamped with the names a block's
    ``step.remat`` keeps, so the recompute of the part replays them.  A
    decision has no backward, so making it again bought nothing, and a
    ``top_k`` taken again from recomputed scores could pick another
    expert at a near tie: the backward of a routing the loss never ran."""
    config: Any

    @nn.compact
    def __call__(self, h: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        first, held = cfg.experts_held
        batch, seq, embed = h.shape
        flat = h.reshape(batch * seq, embed)
        w_router = self.param(
            "router", nn.with_partitioning(nn.initializers.normal(0.02),
                                           ("embed", None)),
            (embed, cfg.num_experts), cfg.param_dtype)

        def experts(name, shape, axes):
            return self.param(
                name, nn.with_partitioning(nn.initializers.normal(0.02),
                                           axes), shape,
                cfg.param_dtype).astype(cfg.dtype)

        # every op of the layer is in one of its five parts (``step.
        # PARTS``); the rounding of an expert's matrices is the products'
        with step.scope("moe.experts"):
            # an expert's matrices in the order of its products
            into = [experts(name, (held, embed, cfg.expert_dim),
                            ("expert", "embed", "mlp"))
                    for name in (("experts_gate", "experts_up")
                                 if getattr(cfg, "expert_form", "gated")
                                 == "gated" else ("experts_up",))]
            w_down = experts("experts_down", (held, cfg.expert_dim, embed),
                             ("expert", "mlp", "embed"))

        mesh = expert.group_mesh(getattr(cfg, "expert_axis", None))
        if mesh is not None:
            return self._exchanged(mesh, flat, w_router, (*into, w_down),
                                   chosen).reshape(batch, seq, embed)
        with step.scope("moe.route"):
            idx, weights, own = route(cfg, flat, w_router, chosen)
        with step.scope("moe.plan"):
            # buffers for the worst case: every pair may land here
            plan = gm.plan_rows(idx, first, held, block_m=BLOCK_ROWS)
        kept = _kept(plan)
        self.sow("intermediates", "expert_load", plan.sizes)
        self.sow("intermediates", "expert_choice", own)
        # of that buffer's tiles the live ones alone are worked on
        self.sow("intermediates", "live_tiles", plan.n_live[0])
        self.sow("intermediates", "buffer_tiles", plan.tile_expert.shape[0])
        routed = _landed_sums(cfg, flat, weights, (*into, w_down), kept)
        with step.scope("moe.combine"):
            return routed.reshape(batch, seq, embed)

    def _exchanged(self, mesh, flat, w_router, matrices, chosen):
        """The layer as one chip of the group along ``expert_axis``
        (``parallel/expert.py``), under ``shard_map`` over that axis:
        each chip routes its OWN tokens over all published experts, the
        rows are all-gathered with their choices and weights, dispatch,
        the grouped products and combine run as on one chip over the
        GROUP's tokens for the experts this chip owns (``first = its
        index along the axis x held``), and the parts are
        reduce-scattered so that each chip is left with its own tokens'
        sums, in the compute dtype.  ``flat [B*T, E]``: the group's
        tokens, a chip's own together; the router's gradient flows
        through the gathered weights."""
        from jax.sharding import PartitionSpec as P

        cfg = self.config
        axis = cfg.expert_axis
        chips = mesh.shape[axis]
        first, held = cfg.experts_held
        own_held = held // chips

        def local(flat, w_router, matrices, chosen):
            with step.scope("moe.route"):
                idx, weights, own = route(cfg, flat, w_router, chosen)
            with step.scope("moe.exchange"):
                rows, idx, weights = (expert.gather_tokens(a, axis)
                                      for a in (flat, idx, weights))
            with step.scope("moe.plan"):
                # buffers for the worst case: every pair of the group
                # may land here
                plan = gm.plan_rows(
                    idx, first + own_held * jax.lax.axis_index(axis),
                    own_held, block_m=BLOCK_ROWS)
            kept = _kept(plan)
            part = _landed_sums(cfg, rows, weights, matrices, kept)
            with step.scope("moe.exchange"):
                mine = expert.scatter_sums(part, axis)
            return mine, plan.sizes, own, plan.n_live

        rows = P(axis, None)
        mine, sizes, own, n_live = jax.shard_map(
            local, mesh=mesh,
            in_specs=(rows, P(), P(axis, None, None),
                      None if chosen is None else rows),
            out_specs=(rows, P(axis), rows, P(axis)), check_vma=False,
        )(flat, w_router, matrices, chosen)
        # as on one chip, in the group's view: all its experts' loads,
        # the chips' live tiles of the chips' buffers
        pairs = flat.shape[0] * cfg.top_k
        self.sow("intermediates", "expert_load", sizes)
        self.sow("intermediates", "expert_choice", own)
        self.sow("intermediates", "live_tiles", n_live.sum())
        self.sow("intermediates", "buffer_tiles",
                 chips * (-(-pairs // BLOCK_ROWS) + own_held))
        return mine


class AttentionPart(nn.Module):
    """``x + post_norm(attention(pre_norm(x)))``.  A block names it
    ``attn``, and flax puts a module's name around its ops: that IS the
    step's part ``attn`` (``models/step.py``), norms, projections,
    rotation, gate and residual add included."""
    config: AFMoEConfig
    kind: str      # "sliding" | "full"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from ray_tpu.parallel.mesh import get_global_mesh

        cfg = self.config
        batch, seq = x.shape[:2]
        heads, kv, dim = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        norm = functools.partial(RMSNorm, cfg.rms_eps)

        # the part's pieces (``step.ATTN_PIECES``); the kernels' call
        # names its own two inside its kind
        with step.scope("attn.norm"):
            h = norm(name="attn_norm")(x)
        with step.scope("attn.proj"):
            q = _dense(cfg, heads * dim, "wq", ("embed", "heads"))(h)
            k = _dense(cfg, kv * dim, "wk", ("embed", "kv"))(h)
            v = _dense(cfg, kv * dim, "wv", ("embed", "kv"))(h)
            gate = _dense(cfg, heads * dim, "wg", ("embed", "heads"))(h)
        with step.scope("attn.norm"):  # (a view by heads moves nothing)
            q = _HeadNorm(cfg.rms_eps, name="q_norm")(
                q.reshape(batch, seq, heads, dim))
            k = _HeadNorm(cfg.rms_eps, name="k_norm")(
                k.reshape(batch, seq, kv, dim))
            v = v.reshape(batch, seq, kv, dim)
        sliding = self.kind == "sliding"
        if sliding:
            with step.scope("attn.pos"):
                q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        with step.scope("attn." + self.kind):
            attn = flash_attention(
                q, k, v, causal=True, mesh=get_global_mesh(),
                window=cfg.window if sliding else None)
        with step.scope("attn.gate"):
            attn = attn.reshape(batch, seq, heads * dim) * nn.sigmoid(gate)
        with step.scope("attn.proj"):
            attn = _dense(cfg, cfg.embed_dim, "wo", ("heads", "embed"))(attn)
        with step.scope("attn.norm"):
            return x + norm(name="attn_post_norm")(attn)


class MLPPart(nn.Module):
    """``x + post_norm(mlp(pre_norm(x)))``: the dense SwiGLU of a leading
    layer, or the shared expert plus the routed experts held here.  The
    norms, the dense or shared SwiGLU and the residual add are the step's
    part ``mlp``; the routed experts are their own five parts BESIDE it,
    so the module names its parts itself (``step.names_its_parts``)."""
    config: AFMoEConfig
    routed: bool
    names_its_parts = True

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        with step.named_children():
            with step.scope("mlp"):
                h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
                out = _swiglu(cfg, h, cfg.expert_dim, "shared_") \
                    if self.routed else _swiglu(cfg, h, cfg.dense_dim, "w_")
            if self.routed:
                routed = RoutedExperts(cfg, name="moe")(h, chosen)
            with step.scope("mlp"):
                if self.routed:
                    out = out + routed
                return x + RMSNorm(cfg.rms_eps, name="mlp_post_norm")(out)


def each_sequence(parts, x: jax.Array,
                  chosen: Optional[jax.Array] = None,
                  around: Tuple[str, str] = ("attn.norm", "mlp")
                  ) -> jax.Array:
    """A layer's ``parts``, one after the other, over ONE sequence of the
    batch at a time (a kernel call a sequence, the activation memory of
    one sequence); ``chosen [B*T, k]``: the routing each sequence's LAST
    part, the routed one, replays.  ``around``: the step's parts
    (``models/step.py``) that taking a sequence out of the batch and
    joining the sequences again are put down to: the layer's first and
    its last (of ``attn``, the piece that holds the residual stream's
    side)."""
    seq = x.shape[1]
    out = []
    for i in range(x.shape[0]):
        with step.scope(around[0]):
            h = x[i:i + 1]
        for part in parts[:-1]:
            with step.names_its_parts(part):
                h = part(h)
        with step.names_its_parts(parts[-1]):
            out.append(parts[-1](h) if chosen is None else
                       parts[-1](h, chosen[i * seq:(i + 1) * seq]))
    with step.scope(around[1]):
        return jnp.concatenate(out)


class AFMoEBlock(nn.Module):
    """One layer: its two parts, each over one sequence at a time and
    each recomputed on its own in the backward pass under ``remat``
    (``step.remat``): the backward of a part then holds that part's
    activations for 8,192 tokens alone (both parts over a batch of two
    are 3 GiB, which the training state leaves no room for beside a
    gradient check).  What a routed call DECIDED is not recomputed: its
    choices and its row plan are kept from the forward (under 1 MB a
    call at the cell's shapes) and the recompute replays them, so the
    backward is of the routing the loss ran, and ``plan_rows`` and the
    router's ``top_k`` run once a call."""
    config: AFMoEConfig
    kind: str      # "sliding" | "full"
    routed: bool   # an expert layer, or a leading dense one

    @nn.compact
    def __call__(self, x: jax.Array,
                 chosen: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        attn, mlp = AttentionPart, MLPPart
        if cfg.remat == "full":
            attn, mlp = step.remat(attn), step.remat(mlp)
        return each_sequence((attn(cfg, self.kind, name="attn"),
                              mlp(cfg, self.routed, name="mlp")), x, chosen)


class AFMoE(nn.Module):
    config: AFMoEConfig

    @nn.compact
    def hidden(self, tokens: jax.Array,
               choices: Optional[List[jax.Array]] = None):
        """Final normed hidden states (float32) and the untied head
        ``[V, E]``: the loss runs them through the chunked LM head.
        ``choices``: per expert layer the experts ``[B*T, k]`` to route
        every token to, as :func:`router_choices` gives them, in place
        of the routers' own."""
        cfg = self.config
        embed = self.param(
            "embed", nn.with_partitioning(nn.initializers.normal(0.02),
                                          ("vocab", "embed")),
            (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)
        head = self.param(
            "head", nn.with_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)
        with step.scope("embed"):
            x = embed.astype(cfg.dtype)[tokens]
            if cfg.mup:
                x = x * jnp.asarray(math.sqrt(cfg.embed_dim), cfg.dtype)
        # the timeline says what was compiled: one span around the trace
        # of the layers (a call of a layer sees one sequence)
        with telemetry.span("model", "moe.plan",
                            **cfg.plan_args(tokens.shape[1])):
            for n, kind in enumerate(cfg.layer_kinds()):
                i = n - cfg.num_dense_layers
                block = AFMoEBlock(cfg, kind, i >= 0,
                                   name=f"h{i}" if i >= 0 else f"dense{n}")
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


def loss_fn(model: nn.Module, params, tokens: jax.Array,
            head_chunk: int = 2048,
            head_logits_dtype: Any = None,
            choices: Optional[List[jax.Array]] = None,
            with_choices: bool = False):
    """Next-token cross entropy over the vocabulary (slice) through the
    chunked LM head; float32 logits unless ``head_logits_dtype`` says
    otherwise.  The source's auxiliary load-balance term is left out
    (``assumed`` in the configuration).  ``choices``: a recorded routing
    to replay (:meth:`AFMoE.hidden`).  ``with_choices``: also what every
    expert layer's router chose ITSELF on the way, ``[B*T, k]`` a layer,
    as :func:`router_choices` gives it.  ``model``: any module with
    AFMoE's ``hidden(tokens, choices)`` whose expert layers are
    ``h<i>/mlp/moe`` (a :class:`RoutedExperts`)."""
    from ray_tpu.ops.fused import chunked_lm_loss

    out = model.apply({"params": params}, tokens, choices,
                      method=type(model).hidden,
                      mutable=["intermediates"] if with_choices else False)
    (x, head), state = out if with_choices else (out, None)
    compute = jnp.bfloat16 if model.config.dtype == jnp.bfloat16 else None
    # the scan's body inherits the name, and the gradient's two products
    # with it: they run in the forward's scan (``ops/fused.py``)
    with step.scope("head"):
        loss = chunked_lm_loss(x[:, :-1], head, tokens[:, 1:],
                               chunk=head_chunk, compute_dtype=compute,
                               logits_dtype=head_logits_dtype)
    return (loss, _own_choices(model, state)) if with_choices else loss


def _expert_layers(cfg) -> int:
    """Expert layers ``h0 ..`` of a stack: ``num_layers`` unless the
    configuration counts them apart (``models/qwen3_next.py``, whose
    depth argument counts one kind of its layers)."""
    return getattr(cfg, "num_expert_layers", cfg.num_layers)


def _own_choices(model: nn.Module, state) -> List[jax.Array]:
    # sown once a call, and a call sees one sequence
    return [jnp.concatenate(
        state["intermediates"][f"h{i}"]["mlp"]["moe"]["expert_choice"])
        for i in range(_expert_layers(model.config))]


def make_train_step(model: nn.Module, tx):
    """The donated ``(params, opt_state, tokens) -> (params, opt_state,
    loss)`` step, GPT-2's (``models/step.py``)."""
    return step.make_train_step(functools.partial(loss_fn, model), tx,
                                remat=model.config.remat)


@functools.partial(jax.jit, static_argnums=0)
def router_stats(model: nn.Module, params, tokens: jax.Array
                 ) -> Dict[str, jax.Array]:
    """What a routed layer must tell its operator, per expert layer (in
    order): ``load [L, held]`` (token, choice) pairs that chose each held
    expert; ``landed_share [L]`` of all pairs that land here (an even
    router gives ``held / experts``), which on a TPU is also what the
    layer's sums over tokens COST: ``combine``, its ``d_w`` and
    ``dispatch``'s backward read ``landed_share x pairs`` rows (a group
    of 8 each) where they read ``pairs`` (``ops/grouped_matmul.py``
    ``_walk_pallas``); ``imbalance [L]`` largest load over
    mean load; ``live_tiles [L]`` and ``buffer_tiles [L]``: row tiles
    that held rows, which the layer worked on, of those its worst-case
    buffers span.  For a training loop to pass to
    :func:`report_router_stats` and ``session.report``."""
    cfg = model.config
    _, state = model.apply({"params": params}, tokens,
                           method=type(model).hidden,
                           mutable=["intermediates"])
    layers = state["intermediates"]
    # sown once a call, and a call sees one sequence
    load = jnp.stack([
        sum(layers[f"h{i}"]["mlp"]["moe"]["expert_load"])
        for i in range(_expert_layers(cfg))]).astype(jnp.float32)
    tiles = {k: jnp.stack([sum(layers[f"h{i}"]["mlp"]["moe"][k])
                           for i in range(_expert_layers(cfg))])
             for k in ("live_tiles", "buffer_tiles")}
    pairs = tokens.shape[0] * tokens.shape[1] * cfg.top_k
    return {"load": load, "landed_share": load.sum(-1) / pairs,
            "imbalance": load.max(-1) / jnp.maximum(load.mean(-1), 1e-9),
            **tiles}


@functools.partial(jax.jit, static_argnums=0)
def router_choices(model: nn.Module, params, tokens: jax.Array
                   ) -> List[jax.Array]:
    """The experts every expert layer's router chose, ``[B*T, k]`` a
    layer: for comparing a near tie with a reference's own choice."""
    _, state = model.apply({"params": params}, tokens,
                           method=type(model).hidden,
                           mutable=["intermediates"])
    return _own_choices(model, state)


def report_router_stats(stats: Dict[str, Any], model_name: str = "afmoe"
                        ) -> Dict[str, float]:
    """Host side: the stats as gauges (tagged ``model_name``: another
    model's module binds its own, ``models/deepseek_v3.py``), and as flat
    scalars for ``session.report``.  ``moe/h<layer>/landed_share`` is
    the share of a call's pairs whose row the sums over tokens fetch:
    their cost goes with it, so a chip that draws a hot expert pays
    more there (:func:`router_stats`)."""
    import numpy as np

    out: Dict[str, float] = {}
    for layer, (load, share, imb, live, buffer) in enumerate(zip(*(
            np.asarray(stats[k]) for k in (
                "load", "landed_share", "imbalance", "live_tiles",
                "buffer_tiles")))):
        live_share = float(live) / float(buffer)
        telemetry.moe_router_load(model_name, layer, load.tolist(),
                                  float(share), float(imb), live_share)
        out[f"moe/h{layer}/landed_share"] = float(share)
        out[f"moe/h{layer}/imbalance"] = float(imb)
        out[f"moe/h{layer}/live_share"] = live_share
    return out
