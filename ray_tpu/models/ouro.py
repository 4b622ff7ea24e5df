"""Looped decoder (``model_type`` ``ouro``; ByteDance's Ouro-2.6B is the
configuration the benchmark runs) in flax linen, for the training path.

What the model is, as the public ``config.json`` keys fix it (what they
leave open is listed under ``assumed`` in ``benchmarks/configs/
ouro-2.6b.json``), for one sequence ``x [T, hidden]``:

* a layer, sandwich-normed: ``a = x + N2(Attn(N1(x)))``, ``y = a +
  N4(MLP(N3(a)))``, four RMS norms; attention with as many K/V heads as
  query heads, rotary embedding over the whole head (halves rotated,
  ``models/llama.py`` ``_rope``), causal, no window; SwiGLU;
* THE LOOP: ``h_0 = Embed(tokens)``, ``h_t = N_f(Stack(h_{t-1}))`` for
  ``t = 1 .. passes`` (``total_ut_steps``): the same ``Stack`` of layers
  and the same final norm every pass, over ONE set of parameters (the
  same module instances are called ``passes`` times, so a weight's
  gradient is the sum over its uses); the normed state is what the next
  pass reads;
* an exit at every pass: logits ``W_head h_t`` (untied, float32) and a
  gate ``lambda_t = sigmoid(w_g . h_t + b_g)``, one ``Linear(hidden,
  1)`` shared by the passes.  A token's exit distribution: ``p_t =
  lambda_t prod_{j<t} (1 - lambda_j)``, the last exit taking the rest;
* the loss, mean over tokens: ``sum_t p_t CE_t - beta H(p)``, every
  token through every pass (``early_exit_threshold`` is an inference
  key); gradients reach the gate through ``p``.

Every layer runs its two parts over one sequence of the batch at a
time, each recomputed on its own under ``remat`` (``models/afmoe.py``
``each_sequence``).  What the loop costs is activations, not state: a
part's saved input for every one of ``passes x layers`` layer-calls.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.core import telemetry
from ray_tpu.models import step
from ray_tpu.models.afmoe import _dense, _swiglu, each_sequence
from ray_tpu.models.llama import RMSNorm, _rope
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.fused import chunked_token_loss, weighted_token_loss


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    #: the sequence as run (``max_position_embeddings``, 65536, only
    #: bounds it: RoPE needs no table)
    max_seq_len: int = 4096
    #: layers of the stack; every pass runs all of them
    num_layers: int = 48
    #: ``total_ut_steps``: how often the stack is applied to its own
    #: (normed) output
    passes: int = 4
    num_heads: int = 16
    head_dim: int = 128
    embed_dim: int = 2048
    mlp_dim: int = 5632
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    #: weight of the exit distribution's entropy in the loss
    exit_beta: float = 0.05
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: "" | "full": each part of a layer-call recomputed in the backward
    #: pass from its saved input
    remat: str = ""

    @classmethod
    def ouro_2_6b(cls, **kw) -> "OuroConfig":
        return cls(**kw)

    @classmethod
    def ouro_2_6b_stage(cls, **kw) -> "OuroConfig":
        """One pipeline stage of eight (``benchmarks/configs/
        ouro-2.6b.json``): 6 of the 48 layers, run four times as the
        whole model runs its 48, with the embedding and the head; every
        width, head and vocabulary row as published."""
        defaults = dict(num_layers=6, max_seq_len=4096)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "OuroConfig":  # for tests
        defaults = dict(vocab_size=256, max_seq_len=64, num_layers=2,
                        num_heads=4, head_dim=16, embed_dim=64, mlp_dim=96)
        defaults.update(kw)
        return cls(**defaults)

    def plan_args(self, batch: int, seq: int) -> Dict[str, Any]:
        """What loop was compiled, for the ``loop.plan`` span.
        ``saved_bytes``: the inputs of the recomputed parts that the
        forward pass leaves for the backward (one residual-stream state
        a part a layer-call a sequence), as reckoned from the shapes."""
        calls = self.passes * self.num_layers
        state = batch * seq * self.embed_dim * jnp.dtype(self.dtype).itemsize
        return {"passes": self.passes, "layers": self.num_layers,
                "layer_calls": calls, "head_calls": self.passes,
                "remat": "part" if self.remat == "full" else "none",
                "saved_bytes": 2 * calls * state}


class _Part(nn.Module):
    """A sub-layer between its two norms."""
    config: OuroConfig

    def sandwich(self, x: jax.Array, inner, name: str) -> jax.Array:
        """``x + post_norm(inner(pre_norm(x)))``; of ``attn`` the norms
        and the sum are the piece ``attn.norm`` (``step.ATTN_PIECES``:
        ``mlp`` is split no further)."""
        eps = self.config.rms_eps

        def norms():
            return step.scope("attn.norm") if name == "attn" \
                else contextlib.nullcontext()

        with norms():
            h = RMSNorm(eps, name=name + "_norm")(x)
        out = inner(h)
        with norms():
            return x + RMSNorm(eps, name=name + "_post_norm")(out)


class AttentionPart(_Part):
    """``x + post_norm(attention(pre_norm(x)))``.  A block names it
    ``attn``, and flax puts a module's name around its ops: that IS the
    step's part ``attn`` (``models/step.py``); the pass it is called in
    is named INSIDE it, so the part stays outermost."""

    @nn.compact
    def __call__(self, x: jax.Array, pass_index: int) -> jax.Array:
        cfg = self.config
        batch, seq = x.shape[:2]
        heads, dim = cfg.num_heads, cfg.head_dim

        def attention(h):
            # the part's pieces; the kernels' call names its own two
            with step.scope("attn.pos"):
                positions = jnp.broadcast_to(jnp.arange(seq)[None],
                                             (batch, seq))
            with step.scope("attn.proj"):
                q, k, v = [_dense(cfg, heads * dim, name,
                                  ("embed", "heads"))(h).reshape(
                    batch, seq, heads, dim) for name in ("wq", "wk", "wv")]
            with step.scope("attn.pos"):
                q = _rope(q, positions, cfg.rope_theta)
                k = _rope(k, positions, cfg.rope_theta)
            with step.scope("attn.full"):
                attn = flash_attention(q, k, v, causal=True)
            with step.scope("attn.proj"):
                return _dense(cfg, cfg.embed_dim, "wo",
                              ("heads", "embed"))(
                    attn.reshape(batch, seq, heads * dim))

        with jax.named_scope(f"pass{pass_index}"):
            return self.sandwich(x, attention, "attn")


class MLPPart(_Part):
    """``x + post_norm(swiglu(pre_norm(x)))``: the step's part ``mlp``."""

    @nn.compact
    def __call__(self, x: jax.Array, pass_index: int) -> jax.Array:
        cfg = self.config
        with jax.named_scope(f"pass{pass_index}"):
            return self.sandwich(
                x, lambda h: _swiglu(cfg, h, cfg.mlp_dim, "w_"), "mlp")


class OuroBlock(nn.Module):
    """One layer: its two parts, each over one sequence at a time and
    each recomputed on its own in the backward pass under ``remat``, as
    ``afmoe.AFMoEBlock``; called once a pass."""
    config: OuroConfig
    #: the two parts (a class attribute, no field: a benchmark's control
    #: stands in for them in a subclass)
    parts = (AttentionPart, MLPPart)

    def setup(self):
        attn, mlp = self.parts
        if self.config.remat == "full":
            # argument 0 is the module, 2 the pass: a name, not a tracer
            attn = nn.remat(attn, static_argnums=(2,))
            mlp = nn.remat(mlp, static_argnums=(2,))
        self.attn, self.mlp = attn(self.config), mlp(self.config)

    def __call__(self, x: jax.Array, pass_index: int) -> jax.Array:
        return each_sequence(
            (lambda h: self.attn(h, pass_index),
             lambda h: self.mlp(h, pass_index)), x)


class Ouro(nn.Module):
    config: OuroConfig
    #: a layer (a class attribute, as ``OuroBlock.parts``)
    Block = OuroBlock

    @staticmethod
    def reads(raw: jax.Array, normed: jax.Array) -> jax.Array:
        """What the next pass reads of what a pass leaves: the NORMED
        state."""
        return normed

    @nn.compact
    def hidden(self, tokens: jax.Array
               ) -> Tuple[List[jax.Array], jax.Array, jax.Array]:
        """``(h_1 .. h_R, head, gate)``: the normed state every pass
        leaves (``[B, T, E]`` each, as the next pass reads it: a chunk of
        the head reads it in float32), the untied head ``[V, E]``
        and the exit gate's logit at every pass but the last, whose exit
        takes what is left (``[R - 1, B, T]`` float32)."""
        cfg = self.config

        def table(name):
            return self.param(
                name, nn.with_partitioning(nn.initializers.normal(0.02),
                                           ("vocab", "embed")),
                (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype)

        embed, head = table("embed"), table("head")
        with step.scope("embed"):
            # the rows, then the cast: no rounded copy of the table
            x = embed[tokens].astype(cfg.dtype)
        # ONE set of modules, called once a pass
        blocks = [self.Block(cfg, name=f"h{i}")
                  for i in range(cfg.num_layers)]
        final_norm = RMSNorm(cfg.rms_eps, name="final_norm")
        gate = nn.Dense(1, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                        kernel_init=nn.with_partitioning(
                            nn.initializers.normal(0.02), ("embed", None)),
                        name="exit_gate")
        states, gates = [], []
        # the timeline says what was compiled: one span around the trace
        # of the loop
        with telemetry.span("model", "loop.plan", **cfg.plan_args(
                *tokens.shape)):
            for t in range(cfg.passes):
                for block in blocks:
                    x = block(x, t)
                # the final norm is the head's, as in every model here;
                # the next pass reads the normed state
                with step.scope("head"), jax.named_scope(f"pass{t}"):
                    normed = final_norm(x)
                    states.append(normed)
                    x = self.reads(x, normed)
                if t < cfg.passes - 1:
                    with step.scope("exit"), jax.named_scope(f"pass{t}"):
                        gates.append(gate(normed.astype(jnp.float32))[..., 0])
        return states, head, jnp.stack(gates)

    def __call__(self, tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """``(logits [R, B, T, V], log p [R, B, T])`` of every exit."""
        states, head, gate = self.hidden(tokens)
        logits = jnp.einsum(
            "rbte,ve->rbtv", jnp.stack(states).astype(jnp.float32),
            head.astype(jnp.float32))
        return logits, exit_log_p(gate)

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: int = 0):
        seq = seq or self.config.max_seq_len
        tokens = jnp.zeros((batch, seq), jnp.int32)
        return self.init(rng, tokens, method=type(self).hidden)["params"]


def exit_log_p(gate: jax.Array) -> jax.Array:
    """``log p_t`` of the exit distribution, ``[R, ...]`` from the gate's
    logits at the first ``R - 1`` passes ``[R - 1, ...]``: ``p_t =
    lambda_t prod_{j<t} (1 - lambda_j)`` with ``lambda = sigmoid(gate)``,
    and the last exit takes what is left, ``prod_{j<R} (1 - lambda_j)``.
    Float32, from log-sigmoids: no product of small numbers."""
    gate = gate.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate), axis=0)   # j <= t
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay])  # j < t
    leave = jnp.concatenate(
        [jax.nn.log_sigmoid(gate), jnp.zeros_like(gate[:1])])
    return before + leave


def exit_loss(ce: jax.Array, gate: jax.Array, beta: float) -> jax.Array:
    """A token's loss, ``[...]``: ``sum_t p_t CE_t - beta H(p)`` from its
    cross entropy at every exit ``ce [R, ...]`` and its gate logits
    ``[R - 1, ...]``; ``H(p) = -sum_t p_t log p_t``."""
    log_p = exit_log_p(gate)
    return jnp.sum(jnp.exp(log_p) * (ce + beta * log_p), axis=0)


def _exits(model: nn.Module, params, tokens: jax.Array):
    """``(hidden [R x B, T-1, E], head [V, E], labels [R x B, T-1], gate
    [R-1, B, T-1])``: the exits' states STACKED for one call of the
    head (one scan, one rounded copy of the head and one accumulator of
    its gradient, where a call an exit keeps four of each alive: 1.5
    GiB more; PERF.md, PR 47), every exit's labels, and the gate's
    logits.  The last position has no label and is left out."""
    states, head, gate = model.apply({"params": params}, tokens,
                                     method=type(model).hidden)
    return (jnp.concatenate([x[:, :-1] for x in states]), head,
            jnp.tile(tokens[:, 1:], (len(states), 1)), gate[:, :, :-1])


def exit_terms(model: nn.Module, params, tokens: jax.Array,
               head_chunk: int = 1024, head_logits_dtype: Any = None
               ) -> Tuple[jax.Array, jax.Array]:
    """``(ce [R, B, T-1], gate [R-1, B, T-1])``: every token's next-token
    cross entropy at every exit, through the chunked head that gives a
    loss a token (float32 logits unless ``head_logits_dtype`` says
    otherwise, the ``[chunk, V]`` block alive in one scan step), and its
    gate logits.  For a reader of the exits and for a loss that weighs
    them its own way (``benchmarks/controls/ouro.py``); the training
    loss, :func:`loss_fn`, does not come through here: a cotangent a
    token is known only in the backward pass, so this head recomputes
    its logits there."""
    hidden, head, labels, gate = _exits(model, params, tokens)
    compute = jnp.bfloat16 if model.config.dtype == jnp.bfloat16 else None
    # the scan's body inherits the part's name and cannot carry a pass
    with step.scope("head"):
        ce = chunked_token_loss(hidden, head, labels, chunk=head_chunk,
                                compute_dtype=compute,
                                logits_dtype=head_logits_dtype)
    return ce.reshape(-1, *gate.shape[1:]), gate


def loss_fn(model: nn.Module, params, tokens: jax.Array,
            head_chunk: int = 1024, head_logits_dtype: Any = None
            ) -> jax.Array:
    """Mean over tokens of ``sum_t p_t CE_t - beta H(p)``: the loss read
    at every exit, weighted by the exit distribution the gate computes,
    which takes gradients itself.  The head weighs every (exit, token)
    by ``p_t / n`` (``weighted_token_loss``: loss and gradient in one
    scan over the logits, the gate's gradient through the weights, whose
    cotangent is ``CE``); the entropy term stands beside it."""
    hidden, head, labels, gate = _exits(model, params, tokens)
    compute = jnp.bfloat16 if model.config.dtype == jnp.bfloat16 else None
    n = gate[0].size
    with step.scope("exit"):
        log_p = exit_log_p(gate)
        p = jnp.exp(log_p)
    with step.scope("head"):
        loss = weighted_token_loss(
            hidden.reshape(-1, hidden.shape[-1]), head, labels.reshape(-1),
            (p / n).reshape(-1), chunk=head_chunk, compute_dtype=compute,
            logits_dtype=head_logits_dtype)
    with step.scope("exit"):
        return loss + model.config.exit_beta * jnp.sum(p * log_p) / n


def make_train_step(model: nn.Module, tx):
    """The donated ``(params, opt_state, tokens) -> (params, opt_state,
    loss)`` step, GPT-2's (``models/step.py``)."""
    return step.make_train_step(functools.partial(loss_fn, model), tx,
                                remat=model.config.remat)


@functools.partial(jax.jit, static_argnums=0)
def exit_stats(model: nn.Module, params, tokens: jax.Array
               ) -> Dict[str, jax.Array]:
    """What a looped model must tell its operator about a batch:
    ``exit_share [R]`` the mean exit distribution, ``expected_passes``
    the mean ``sum_t t p_t`` (the depth a token would be given by its
    gate), ``exit_entropy`` the mean ``H(p)``.  For a training loop to
    pass to :func:`report_exit_stats` and ``session.report``."""
    _, _, gate = model.apply({"params": params}, tokens,
                             method=type(model).hidden)
    log_p = exit_log_p(gate)
    p = jnp.exp(log_p)
    share = p.reshape(p.shape[0], -1).mean(-1)
    return {"exit_share": share,
            "expected_passes": jnp.sum(
                share * jnp.arange(1, p.shape[0] + 1)),
            "exit_entropy": -jnp.sum(p * log_p, axis=0).mean()}


def report_exit_stats(stats: Dict[str, Any]) -> Dict[str, float]:
    """Host side: the stats as the ``ray_tpu_loop_*`` gauges (tagged
    ``model="ouro"``), and as flat scalars for ``session.report``."""
    import numpy as np

    shares = [float(s) for s in np.asarray(stats["exit_share"])]
    expected = float(stats["expected_passes"])
    entropy = float(stats["exit_entropy"])
    telemetry.loop_exits("ouro", shares, expected, entropy)
    out = {f"loop/exit{t + 1}/share": s for t, s in enumerate(shares)}
    out["loop/expected_passes"] = expected
    out["loop/exit_entropy"] = entropy
    return out
