"""Operations and bytes the Ouro (Ouro-2.6B) cell's algorithms need, from
shapes alone: the benchmark's own copies, as ``costs_afmoe.py``,
``costs_deepseek_v3.py`` and ``costs_nemotron_h.py`` are for the cells
before it.

``c`` is the configuration file's dict (``benchmarks/configs/
ouro-2.6b.json``): the source's key names, as run.  The stack of
``num_hidden_layers`` layers runs ``total_ut_steps`` times over one set
of parameters, so a step's work counts LAYER-CALLS (layers x passes) and
its state counts layers.
"""

from __future__ import annotations

from typing import Dict

from benchmarks import costs


def layer_matrices(c: Dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` and the SwiGLU's three."""
    e, heads = c["hidden_size"], c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return 2 * e * heads + 2 * e * kv + 3 * e * c["intermediate_size"]


def num_params(c: Dict) -> int:
    """Parameters as held on the chip: the layers ONCE however often they
    run (four norm scales a layer), the untied embedding and head, the
    final norm and the exit gate's vector and bias."""
    e = c["hidden_size"]
    layer = layer_matrices(c) + 4 * e
    return (c["num_hidden_layers"] * layer + 2 * c["vocab_size"] * e
            + e + e + 1)


def layer_calls(c: Dict) -> int:
    return c["num_hidden_layers"] * c["total_ut_steps"]


def forward_flops_per_token(c: Dict, seq: int) -> Dict[str, float]:
    """Forward multiply-adds x 2 a token: the products of every
    layer-call, attention over the VISIBLE (query, key) pairs only (a
    position sees ``(seq + 1) / 2`` keys on average; a pair costs a
    score and a value product a head), a head at every exit, the gate."""
    e = c["hidden_size"]
    pair = 2 * 2.0 * c["num_attention_heads"] * c["head_dim"]
    exits = c["total_ut_steps"]
    return {"layers": layer_calls(c) * 2.0 * layer_matrices(c),
            "attention": layer_calls(c) * pair * (seq + 1) / 2,
            "heads": exits * 2.0 * c["vocab_size"] * e,
            "gate": (exits - 1) * 2.0 * e}


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward and backward (twice the forward: a product's two
    gradients) a token; what ``remat`` computes again is not counted."""
    return 3.0 * sum(forward_flops_per_token(c, seq).values())


def flash_step_cost(c: Dict, batch: int, seq: int, remat: bool
                    ) -> Dict[str, float]:
    """All flash calls of one train step: a call a layer-call a sequence
    (``models/afmoe.py`` ``each_sequence``), each a forward (twice under
    remat), one dK/dV and one dQ; causal, so half the pairs; q, k, v, o
    and their cotangents read or written once a head (as many K/V heads
    as query heads: ``costs.flash_call_cost``)."""
    return costs.flash_step_cost(
        layer_calls(c) * batch, 1, seq, c["num_attention_heads"],
        c["head_dim"], remat)
