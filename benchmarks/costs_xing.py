"""Operations and bytes the Xing4.0-29B-A4B cell's algorithms need, from
shapes alone: the benchmark's own copies, as ``costs_deepseek_v3.py`` is
for Kanana-2.

``c`` is the configuration file's dict (``benchmarks/configs/
xing4.0-29b-a4b.json``): the source's key names, as run.  What the
family shares (the latent-attention flash calls' cost a call, a grouped
product's) is taken from ``costs_deepseek_v3.py`` and ``costs_afmoe.py``
at this model's widths.

**The hyper-connections' LEAST bytes** (:func:`hc_step_bytes`): what a
connection cannot do without moving, a token's lane ``C`` elements of
the compute dtype.  Forward: ``X`` read once (``n`` lanes; ``u``, the
coefficients' projection and the norm over ``n C`` all read the same
bytes), ``y`` read once, ``X'`` written once: ``2 n + 1`` lanes.  The
recomputed forward (full remat) reads ``X`` once more and needs no
``X'``: ``n``.  Backward: ``dX'`` read once and ``X`` read once (``dX``,
``dy`` and the coefficients' gradients all read these), ``y`` and ``du``
read once, ``dX`` and ``dy`` written once: ``3 n + 3``.  ``u`` and the
coefficients themselves (``n n + 2 n`` float32 a token) are not counted:
a fused sub-layer would keep the first in fast memory, and the second is
a thousandth of a lane.
"""

from __future__ import annotations

from typing import Dict

from benchmarks import costs_afmoe, costs_deepseek_v3


def _dims(c: Dict) -> Dict[str, int]:
    heads = c["num_attention_heads"]
    n = c["hc_mult"]
    return {"e": c["hidden_size"], "heads": heads,
            "qk": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "rank": c["kv_lora_rank"],
            "q_rank": c["q_lora_rank"],
            "dense": c["first_k_dense_replace"],
            "expert": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "shared": c["n_shared_experts"] * c["moe_intermediate_size"],
            "lanes": n, "coef": n * (n + 2)}


def attention_matrices(c: Dict) -> int:
    """``W_qa`` (hidden x query latent), ``W_qb`` (query latent x heads
    x (nope + rope)), ``W_kva`` (hidden x (latent + rope)), ``W_kvb``
    (latent x heads x (nope + dv)) and ``W_o`` (heads x dv x hidden)."""
    d = _dims(c)
    return (d["e"] * d["q_rank"] + d["q_rank"] * d["heads"] * d["qk"]
            + d["e"] * (d["rank"] + d["rope"])
            + d["rank"] * d["heads"] * (d["nope"] + d["dv"])
            + d["heads"] * d["dv"] * d["e"])


def hc_params(c: Dict) -> int:
    """One connection: ``phi [n C, n n + 2 n]``, its biases, three
    gates."""
    d = _dims(c)
    return d["lanes"] * d["e"] * d["coef"] + d["coef"] + 3


def _layer_params(c: Dict, routed: bool) -> int:
    d = _dims(c)
    e = d["e"]
    outside = (attention_matrices(c) + d["q_rank"] + d["rank"] + 2 * e
               + 2 * hc_params(c))
    if not routed:
        return outside + 3 * e * c["intermediate_size"]
    return (outside + 3 * e * d["shared"]
            + e * c["published"]["n_routed_experts"]
            + c["n_routed_experts"] * 3 * e * c["moe_intermediate_size"])


def num_params(c: Dict) -> int:
    """Parameters of the cut model as held on the chip:
    ``n_routed_experts`` is the count HELD, the router keeps its
    published width.  A layer's norms: two of the hidden width, the
    query latent's and the key/value latent's.  A multi-token module
    (``num_nextn_predict_layers`` as run) is one more expert layer, its
    ``2 C x C`` projection and three norms."""
    d = _dims(c)
    e = d["e"]
    return (d["dense"] * _layer_params(c, False)
            + d["expert"] * _layer_params(c, True)
            + c["num_nextn_predict_layers"] * (
                _layer_params(c, True) + 2 * e * e + 3 * e)
            + 2 * c["vocab_size"] * e + e)


def visible_pairs(seq: int) -> int:
    """(query, key) pairs a causal layer scores."""
    return costs_afmoe.visible_pairs(seq, None)


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of the cut model requires, 3 x
    the forward's 2 a multiply-add; recompute (remat) NOT counted.
    Forward a layer: the five attention projections; the scores (``nope
    + rope`` deep) and the weighted sum (``dv`` wide) over the VISIBLE
    pairs; two connections, each its projection (``n C x (n n + 2 n)``)
    and the lanes' sums (``read`` ``n C``, ``write`` ``n n C + n C``
    multiply-adds; the twenty Sinkhorn steps are 16 numbers a token and
    left out); the dense MLP, or router + shared expert + the routed
    experts a token meets HERE on average (``top_k x held /
    published``); then the head.  The embedding lookup is a gather."""
    d = _dims(c)
    e, n = d["e"], d["lanes"]
    proj = 2 * attention_matrices(c)
    scores = 2 * d["heads"] * (d["qk"] + d["dv"]) * visible_pairs(seq) / seq
    hc = 2 * 2 * (n * e * d["coef"] + n * e + n * n * e + n * e)
    here = (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["published"]["n_routed_experts"])
    expert_mlp = 2 * (e * c["published"]["n_routed_experts"]
                      + 3 * e * d["shared"]
                      + 3 * e * c["moe_intermediate_size"] * here)
    dense_mlp = 2 * 3 * e * c["intermediate_size"]
    forward = ((d["dense"] + d["expert"]) * (proj + scores + hc)
               + d["dense"] * dense_mlp + d["expert"] * expert_mlp
               + 2 * c["vocab_size"] * e)
    return 3.0 * forward


#: the flash calls and the grouped products of a step: Kanana's
#: functions read this file's keys as they read its own (32 heads of 128
#: + 64 against 128 over ``num_hidden_layers`` layers; the expected live
#: rows ``tokens x top_k x held / published`` at ``hidden x
#: moe_intermediate_size``), so they ARE this cell's
mla_flash_step_cost = costs_deepseek_v3.mla_flash_step_cost
expected_live_rows = costs_deepseek_v3.expected_live_rows
gmm_step_cost = costs_deepseek_v3.gmm_step_cost


def hc_call_lanes(n: int, remat: bool) -> int:
    """Lanes (``[T, C]`` arrays of the compute dtype) one connection
    moves at the least over a step, forward, recomputed and backward
    (the module's docstring)."""
    return (2 * n + 1) + (n if remat else 0) + (3 * n + 3)


def hc_step_bytes(c: Dict, batch: int, seq: int, remat: bool,
                  itemsize: int = 2) -> Dict[str, float]:
    """The least bytes all connections of one train step move: two a
    layer and a sequence."""
    d = _dims(c)
    calls = 2 * c["num_hidden_layers"] * batch
    lane = seq * d["e"] * itemsize
    return {"bytes": float(calls * hc_call_lanes(d["lanes"], remat) * lane),
            "calls": calls}
