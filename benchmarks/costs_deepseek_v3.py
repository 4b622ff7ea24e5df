"""Operations and bytes the DeepSeek-V3 (Kanana-2-30B-A3B) cell's
algorithms need, from shapes alone: the benchmark's own copies, as
``costs_afmoe.py`` is for Trinity-Mini.

``c`` is the configuration file's dict (``benchmarks/configs/
kanana-2-30b-a3b.json``): the source's key names, as run.
"""

from __future__ import annotations

from typing import Dict

from benchmarks import costs_afmoe


def _dims(c: Dict) -> Dict[str, int]:
    heads = c["num_attention_heads"]
    return {"e": c["hidden_size"], "heads": heads,
            "qk": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "rank": c["kv_lora_rank"],
            "dense": c["first_k_dense_replace"],
            "expert": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "shared": c["n_shared_experts"] * c["moe_intermediate_size"]}


def attention_matrices(c: Dict) -> int:
    """``W_q`` (hidden x heads x (nope + rope)), ``W_kva`` (hidden x
    (latent + rope)), ``W_kvb`` (latent x heads x (nope + dv)) and
    ``W_o`` (heads x dv x hidden)."""
    d = _dims(c)
    return (d["e"] * d["heads"] * d["qk"] + d["e"] * (d["rank"] + d["rope"])
            + d["rank"] * d["heads"] * (d["nope"] + d["dv"])
            + d["heads"] * d["dv"] * d["e"])


def num_params(c: Dict) -> int:
    """Parameters of the cut model as held on the chip:
    ``n_routed_experts`` is the count HELD, the router keeps its
    published width.  A layer's norms: two of the hidden width and the
    latent's."""
    d = _dims(c)
    e = d["e"]
    attn = attention_matrices(c) + 2 * e + d["rank"]
    dense = attn + 3 * e * c["intermediate_size"]
    expert = (attn + 3 * e * d["shared"]
              + e * c["published"]["n_routed_experts"]
              + c["n_routed_experts"] * 3 * e * c["moe_intermediate_size"])
    return (d["dense"] * dense + d["expert"] * expert
            + 2 * c["vocab_size"] * e + e)


def visible_pairs(seq: int) -> int:
    """(query, key) pairs a causal layer scores."""
    return costs_afmoe.visible_pairs(seq, None)


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of the cut model requires, 3 x
    the forward's 2 a multiply-add; recompute (remat) NOT counted.
    Forward: the four attention projections; the scores (``nope + rope``
    deep) and the weighted sum (``dv`` wide) over the VISIBLE pairs; the
    dense MLP, or router + shared experts + the routed experts a token
    meets HERE on average (``top_k x held / published``); the head.  The
    embedding lookup is a gather."""
    d = _dims(c)
    e = d["e"]
    proj = 2 * attention_matrices(c)
    scores = 2 * d["heads"] * (d["qk"] + d["dv"]) * visible_pairs(seq) / seq
    here = (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["published"]["n_routed_experts"])
    expert_mlp = 2 * (e * c["published"]["n_routed_experts"]
                      + 3 * e * d["shared"]
                      + 3 * e * c["moe_intermediate_size"] * here)
    dense_mlp = 2 * 3 * e * c["intermediate_size"]
    forward = ((d["dense"] + d["expert"]) * (proj + scores)
               + d["dense"] * dense_mlp + d["expert"] * expert_mlp
               + 2 * c["vocab_size"] * e)
    return 3.0 * forward


def mla_flash_call_cost(kind: str, batch: int, seq: int, heads: int,
                        nope: int, rope: int, dim_v: int,
                        itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ONE causal latent-attention flash call of
    ``kind`` over the visible pairs only.  A pair a head costs a
    multiply-add per element of each product the call needs: forward the
    score (``nope + rope``) and the weighted sum (``dv``); dK/dV the
    score, dV (``dv``), dP (``dv``) and dK (``nope + rope``); dQ the
    score, dP and dQ (``nope + rope``).  Bytes: q, ``k_nope``, v, o (and
    their cotangents) once a head, ``k_rope`` (and its gradient) ONCE,
    the float32 row statistics once a head."""
    qk = nope + rope
    depth = {"fwd": qk + dim_v, "dkdv": 2 * qk + 2 * dim_v,
             "dq": 2 * qk + dim_v}[kind]
    flops = 2.0 * batch * heads * visible_pairs(seq) * depth
    # elements a (position, head) of what each call reads and writes
    per_head = {"fwd": qk + nope + dim_v + dim_v,           # q k v | o
                "dkdv": qk + nope + 2 * dim_v + nope + dim_v,  # q k v do | dk dv
                "dq": qk + nope + 2 * dim_v + qk}[kind]     # q k v do | dq
    shared = {"fwd": rope, "dkdv": 2 * rope, "dq": rope}[kind]
    stats = 1 if kind == "fwd" else 2                       # lse | lse delta
    rows = batch * seq
    bytes_ = rows * ((heads * per_head + shared) * itemsize
                     + heads * stats * 4)
    return {"flops": flops, "bytes": float(bytes_)}


def mla_flash_step_cost(c: Dict, batch: int, seq: int, remat: bool
                        ) -> Dict[str, float]:
    """All flash calls of one train step: per layer and per sequence a
    forward (twice under full remat), one dK/dV and one dQ call."""
    d = _dims(c)
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0}
    for kind, n in (("fwd", 2 if remat else 1), ("dkdv", 1), ("dq", 1)):
        cost = mla_flash_call_cost(kind, 1, seq, d["heads"], d["nope"],
                                   d["rope"], d["dv"])
        times = batch * n * c["num_hidden_layers"]
        out["flops"] += times * cost["flops"]
        out["bytes"] += times * cost["bytes"]
        out["calls"] += times
    return out


def expected_live_rows(c: Dict, tokens: int) -> int:
    """(token, choice) pairs that land on the held experts when the
    router is even: ``tokens x top_k x held / published``."""
    return (tokens * c["num_experts_per_tok"] * c["n_routed_experts"]
            // c["published"]["n_routed_experts"])


def gmm_step_cost(c: Dict, batch: int, seq: int, remat: bool
                  ) -> Dict[str, float]:
    """All grouped products of one train step at the EXPECTED live rows
    (``costs_afmoe.gmm_call_cost`` each): per expert layer and per
    sequence three projections (gate, up: hidden x width; down: width x
    hidden), each forward (twice under full remat), d lhs and d rhs."""
    d = _dims(c)
    rows = expected_live_rows(c, seq)
    e, w = d["e"], c["moe_intermediate_size"]
    layers = batch * d["expert"]
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0, "rows": batch * rows}
    for k, n in ((e, w), (e, w), (w, e)):
        for kind, times in (("fwd", 2 if remat else 1), ("dlhs", 1),
                            ("drhs", 1)):
            cost = costs_afmoe.gmm_call_cost(kind, rows, k, n,
                                             c["n_routed_experts"])
            out["flops"] += layers * times * cost["flops"]
            out["bytes"] += layers * times * cost["bytes"]
            out["calls"] += layers * times
    return out
