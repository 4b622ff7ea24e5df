"""Reference engine for the continuous batcher: a jitted toy
autoregressive decoder.

The model is deliberately tiny but *real* for serving purposes: the
step function is an XLA-compiled fixed-shape program (one embedding
gather + a small MLP mixed over the causal prefix), so it exercises
exactly the property the batcher exists to protect — **one compile per
padding bucket** — and its outputs are a deterministic function of the
prompt, so tests can assert that continuous batching never leaks state
across the requests sharing a batch.

``step_delay_s`` adds a host-side sleep per decode step to emulate a
model whose step cost dwarfs dispatch overhead (a 7B-class decode step
is a few ms on a TPU chip).  Because the sleep is paid once per *step*
— not once per request — it makes batching economics realistic on the
CPU bench box: 8 co-scheduled requests share each step's cost.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from ray_tpu.core import device_telemetry as _dt

__all__ = ["ToyDecoder", "ToyDecoderShard", "make_prompt"]


class ToyDecoder:
    """Duck-typed continuous-batching engine (see serve/batching.py).

    Payload: ``{"prompt": [int, ...], "max_new_tokens": int}`` (or a
    bare list of ints).  Result: ``{"prompt_len", "tokens", "text"}``
    where ``tokens`` are the generated ids.

    ``prefill_delay_per_token_s`` emulates the prompt pass of a real
    model (prefill cost scales with prompt length, decode cost with
    step count): in a unified deployment that cost lands on the decode
    loop at admission time — exactly the stall prefill/decode
    disaggregation removes.
    """

    vocab_size = 64
    eos_token = 1
    pad_token = 0

    def __init__(self, dim: int = 32, step_delay_s: float = 0.0,
                 seed: int = 0, prefill_delay_per_token_s: float = 0.0):
        import jax.numpy as jnp
        import numpy as np

        self.dim = dim
        self.step_delay_s = float(step_delay_s)
        self.prefill_delay_per_token_s = float(prefill_delay_per_token_s)
        rng = np.random.default_rng(seed)
        self.trace_count = 0  # python side effect: fires once per compile
        self._install_weights(
            jnp.asarray(
                rng.normal(size=(self.vocab_size, dim)).astype("float32")),
            jnp.asarray(
                rng.normal(size=(dim, dim)).astype("float32")
                / dim ** 0.5),
            jnp.asarray(
                rng.normal(size=(dim, self.vocab_size)).astype("float32")
                / dim ** 0.5))

    def _install_weights(self, embed, w1, w2) -> None:
        """(Re)bind the weights and rebuild the jitted step: the traced
        program captures the arrays as constants, so a weight swap must
        re-jit — mutating ``self._embed`` alone would keep serving the
        OLD model from the compiled cache."""
        import jax
        import jax.numpy as jnp

        self._embed, self._w1, self._w2 = embed, w1, w2

        def _step(tokens, lengths, active):
            self.trace_count += 1  # traced, not executed, per shape
            emb = self._embed[tokens]                      # [B, L, D]
            L = tokens.shape[1]
            pos = jnp.arange(L)[None, :]                   # [1, L]
            mask = (pos < lengths[:, None]).astype(emb.dtype)
            pooled = (emb * mask[..., None]).sum(axis=1) \
                / jnp.maximum(lengths[:, None].astype(emb.dtype), 1.0)
            h = jnp.tanh(pooled @ self._w1)
            logits = h @ self._w2                          # [B, V]
            # greedy, never emitting pad; eos reachable so sequences
            # can terminate early
            logits = logits.at[:, self.pad_token].set(-1e9)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.where(active, nxt, self.pad_token)

        # the compile-accounting wrapper is rebuilt WITH the jit, so its
        # seen-signature set tracks exactly this executable cache (a
        # weight swap's re-trace counts as a fresh "first" compile)
        self._jstep = _dt.instrument_step(jax.jit(_step),
                                          name="toy_decoder.step")

    # -- model-multiplexing hooks (serve/multiplex.py) ---------------------
    def export_weights(self) -> Dict[str, Any]:
        """Snapshot the full weight set as host arrays — what the
        multiplexer seals into the arena so an evicted model reloads by
        ref instead of re-initializing."""
        import numpy as np

        return {"embed": np.asarray(self._embed),
                "w1": np.asarray(self._w1), "w2": np.asarray(self._w2)}

    def load_weights(self, weights: Dict[str, Any]) -> None:
        import jax.numpy as jnp

        self._install_weights(jnp.asarray(weights["embed"]),
                              jnp.asarray(weights["w1"]),
                              jnp.asarray(weights["w2"]))

    # -- engine protocol ---------------------------------------------------
    def begin_request(self, payload: Any) -> Dict[str, Any]:
        if isinstance(payload, dict):
            prompt = list(payload.get("prompt") or [2])
            max_new = int(payload.get("max_new_tokens", 16))
        else:
            prompt = list(payload)
            max_new = 16
        prompt = [int(t) % self.vocab_size for t in prompt] or [2]
        return {"tokens": prompt, "prompt_len": len(prompt),
                "max_new_tokens": max_new}

    def prefill(self, state: Dict[str, Any]) -> Dict[str, Any]:
        """The prompt pass.  The toy model recomputes from tokens so
        there is no tensor state to build — only the COST is modeled
        (per prompt token), which is what the disaggregation and
        prefix-cache benches measure.  ``state["prefix_len"]`` (set by
        the batcher after a prefix-chain match) is the number of prompt
        tokens whose KV pages were adopted from the cache — their
        prefill cost is skipped."""
        if self.prefill_delay_per_token_s > 0:
            skip = int(state.get("prefix_len") or 0)
            charged = max(0, len(state.get("tokens") or ()) - skip)
            time.sleep(self.prefill_delay_per_token_s * charged)
        return state

    def kv_page_payload(self, tokens: List[int]):
        """Per-token KV payload for one page (kv_cache.py hook): the
        embedding rows of the page's tokens, stacked [n, 2, dim] as a
        stand-in for K and V blocks.  Deterministic in the tokens —
        which is why toy requests survive replica migration: any
        replica rebuilds identical pages."""
        import numpy as np

        emb = np.asarray(self._embed)[
            np.asarray(tokens, dtype=np.int32) % self.vocab_size]
        return np.stack([emb, emb], axis=1)

    def step(self, tokens, lengths, active):
        if self.step_delay_s > 0:
            time.sleep(self.step_delay_s)
        return self._jstep(tokens, lengths, active)

    def finish_request(self, state: Dict[str, Any]) -> Dict[str, Any]:
        plen = state["prompt_len"]
        gen = state["tokens"][plen:]
        return {"prompt_len": plen, "tokens": gen,
                "text": " ".join(str(t) for t in gen)}

    @staticmethod
    def _batch_rows(batch):
        """Rows of one warmup batch: numpy batch format is
        ``{column -> array}``; bare arrays/lists pass through."""
        import numpy as np

        if isinstance(batch, dict):
            batch = next(iter(batch.values()))
        rows = np.asarray(batch)
        return rows[None, :] if rows.ndim <= 1 else rows

    def warmup_batch(self, batch) -> int:
        """Serve-warmup hook (serve.warmup): one representative decode
        per corpus batch warms the padding-bucket compiles without
        decoding every row."""
        import numpy as np

        rows = self._batch_rows(batch)
        prompt = [int(t) % self.vocab_size
                  for t in np.ravel(rows[0])[:8].tolist()] or [2]
        self.generate_unbatched({"prompt": prompt, "max_new_tokens": 2})
        return len(rows)

    # -- convenience -------------------------------------------------------
    def generate_unbatched(self, payload: Any) -> Dict[str, Any]:
        """Request-at-a-time decode through the SAME jitted step (batch
        dim 1 pool): what the batched paths' tests compare against."""
        import numpy as np

        state = self.begin_request(payload)
        buckets = [8, 16, 32, 64, 128, 256]
        while True:
            seq = state["tokens"]
            bucket = next((b for b in buckets if len(seq) + 1 <= b),
                          buckets[-1])
            tokens = np.full((1, bucket), self.pad_token, dtype=np.int32)
            tokens[0, :len(seq)] = seq
            lengths = np.asarray([len(seq)], dtype=np.int32)
            active = np.asarray([True])
            nxt = int(np.asarray(self.step(tokens, lengths, active))[0])
            seq.append(nxt)
            done = nxt == self.eos_token \
                or len(seq) - state["prompt_len"] \
                >= state["max_new_tokens"] or len(seq) >= buckets[-1]
            if done:
                return self.finish_request(state)


class ToyDecoderShard(ToyDecoder):
    """Tensor-parallel shard of the toy decoder (the gang-replica
    reference engine; see serve/sharded.py).

    The MLP's hidden dimension is column-sharded megatron-style: rank
    ``r`` of ``world`` holds ``w1[:, r*cols:(r+1)*cols]`` and computes
    its slice of the hidden activations — each output element is the
    same dot product the unsharded engine computes, so the gang's
    generated tokens match the single-chip engine exactly.  Every rank
    derives identical weights from the shared seed (no weight
    broadcast needed); rank 0 additionally keeps the full ``w2`` to
    combine gathered hidden slices into logits.

    Inside each rank the partial matmul runs as ``shard_map`` over the
    process-local device mesh (``ray_tpu.parallel`` machinery), so the
    whole path — gang fan-out across processes, SPMD within a rank —
    exercises the production shape under ``JAX_PLATFORMS=cpu``.

    Gang protocol (duck-typed; serve/sharded.py drives it):

    ``shard_step(tokens, lengths, active) -> h_part [B, cols]``
        This rank's hidden-slice for one decode step.
    ``combine(parts, active) -> next_tokens``  (rank 0 only)
        Concatenate rank-ordered hidden slices, project to logits,
        greedy-pick next tokens.
    """

    def __init__(self, dim: int = 32, step_delay_s: float = 0.0,
                 seed: int = 0, prefill_delay_per_token_s: float = 0.0,
                 rank: int = 0, world: int = 1):
        super().__init__(dim, step_delay_s=step_delay_s, seed=seed,
                         prefill_delay_per_token_s=prefill_delay_per_token_s)
        import jax
        import jax.numpy as jnp

        self.rank = int(rank)
        self.world = int(world)
        if self.world < 1 or dim % self.world:
            raise ValueError(f"dim {dim} not divisible by world {world}")
        cols = dim // self.world
        lo = self.rank * cols
        self._w1_local = self._w1[:, lo:lo + cols]
        embed = self._embed
        self.shard_trace_count = 0

        def _pooled(tokens, lengths):
            emb = embed[tokens]                            # [B, L, D]
            L = tokens.shape[1]
            pos = jnp.arange(L)[None, :]
            mask = (pos < lengths[:, None]).astype(emb.dtype)
            return (emb * mask[..., None]).sum(axis=1) \
                / jnp.maximum(lengths[:, None].astype(emb.dtype), 1.0)

        # SPMD within the rank: shard the local column block over the
        # process-local mesh when it divides evenly (1-device meshes
        # degenerate to plain jit — same math either way)
        matmul = lambda pooled, w1b: jnp.tanh(pooled @ w1b)  # noqa: E731
        try:
            from jax.sharding import PartitionSpec as P

            from ray_tpu.parallel.mesh import (MeshConfig, build_mesh,
                                               shard_map)
            ndev = len(jax.devices())
            if ndev > 1 and cols % ndev == 0:
                mesh = build_mesh(MeshConfig(tp=-1))
                matmul = shard_map(matmul, mesh=mesh,
                                   in_specs=(P(), P(None, "tp")),
                                   out_specs=P(None, "tp"))
        except Exception:  # noqa: BLE001 — no mesh: plain jit path
            pass

        def _shard_step(tokens, lengths):
            self.shard_trace_count += 1  # fires once per compile
            return matmul(_pooled(tokens, lengths), self._w1_local)

        self._jshard = _dt.instrument_step(jax.jit(_shard_step),
                                           name="toy_decoder.shard_step")

        def _combine(h, active):
            logits = h @ self._w2
            logits = logits.at[:, self.pad_token].set(-1e9)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.where(active, nxt, self.pad_token)

        self._jcombine = _dt.instrument_step(jax.jit(_combine),
                                             name="toy_decoder.combine")

    # -- gang protocol -----------------------------------------------------
    def shard_step(self, tokens, lengths, active):
        """One rank's decode-step slice.  ``step_delay_s`` is paid here
        (per shard, concurrently) — each chip's step cost, not a serial
        sum over the gang."""
        import numpy as np

        if self.step_delay_s > 0:
            time.sleep(self.step_delay_s)
        del active  # inactive slots are masked at combine time
        return np.asarray(self._jshard(np.asarray(tokens),
                                       np.asarray(lengths)))

    def combine(self, parts, active):
        import numpy as np

        h = np.concatenate([np.asarray(p) for p in parts], axis=1)
        return self._jcombine(h, np.asarray(active))

    def warmup_batch(self, batch) -> int:
        """Gang-aware warmup: rank 0 cannot run a full decode alone
        (world > 1), so warm THIS rank's shard-step compile across the
        standard buckets instead."""
        import numpy as np

        rows = self._batch_rows(batch)
        for bucket in (8, 16):
            tokens = np.full((1, bucket), self.pad_token, dtype=np.int32)
            self._jshard(tokens, np.asarray([1], dtype=np.int32))
        return len(rows)

    def step(self, tokens, lengths, active):
        """Single-process reference: run every rank's slice locally
        (world=1 makes this the unsharded engine).  The gang path never
        calls this — serve/sharded.py fans ``shard_step`` out instead."""
        if self.world == 1:
            if self.step_delay_s > 0:
                time.sleep(self.step_delay_s)
            return self.combine([self._jshard(tokens, lengths)], active)
        raise RuntimeError(
            "a ToyDecoderShard with world > 1 only serves through a "
            "gang (serve/sharded.py)")


def make_prompt(i: int, length: Optional[int] = None) -> List[int]:
    """Deterministic per-request prompt (bench/test helper)."""
    n = length if length is not None else 3 + (i % 5)
    return [2 + ((i * 7 + j) % 60) for j in range(n)]
