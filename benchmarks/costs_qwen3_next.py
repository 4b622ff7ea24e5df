"""Operations and bytes the Qwen3-Next (Qwen3-Next-80B-A3B-Instruct)
cell's algorithms need, from shapes alone: the benchmark's own copies, as
``costs_nemotron_h.py`` is for the hybrid cell before it.

``c`` is the configuration file's dict (``benchmarks/configs/
qwen3-next-80b-a3b.json``): the source's key names, as run; the pattern
as run is ``c["as_run"]["pattern"]``.
"""

from __future__ import annotations

from typing import Dict

from benchmarks import costs_afmoe

#: positions of a chunk of the gated delta rule's scan as the
#: configuration's ``assumed.scan`` states it (the source has no key)
CHUNK = 64


def _dims(c: Dict) -> Dict[str, int]:
    pattern = c["as_run"]["pattern"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    return {"e": c["hidden_size"], "hk": hk, "hv": hv, "dk": dk, "dv": dv,
            "key": hk * dk, "value": hv * dv,
            "conv_dim": 2 * hk * dk + hv * dv,
            "taps": c["linear_conv_kernel_dim"],
            "q": c["num_attention_heads"] * c["head_dim"],
            "kv": c["num_key_value_heads"] * c["head_dim"],
            "linear": pattern.count("L"), "full": pattern.count("F"),
            "layers": len(pattern)}


def mixer_matrices(c: Dict) -> int:
    """``W_qkvz`` (hidden x (q k v | z)), ``W_ba`` and ``W_out``."""
    d = _dims(c)
    return d["e"] * (d["conv_dim"] + d["value"] + 2 * d["hv"]) \
        + d["value"] * d["e"]


def attention_matrices(c: Dict) -> int:
    """``W_q`` (q and the output gate), ``W_k``, ``W_v``, ``W_o``."""
    d = _dims(c)
    return 3 * d["e"] * d["q"] + 2 * d["e"] * d["kv"]


def expert_layer_matrices(c: Dict, experts: float) -> float:
    """The router (its published width), the shared expert and its gate,
    and ``experts`` routed experts of three matrices."""
    e = c["hidden_size"]
    return (e * c["published"]["num_experts"]
            + 3 * e * c["shared_expert_intermediate_size"] + e
            + experts * 3 * e * c["moe_intermediate_size"])


def num_params(c: Dict) -> int:
    """Parameters of the cut model as held on the chip: ``num_experts``
    is the count HELD, the router keeps its published width.  A mixer
    besides its matrices: the convolution (no bias), ``A_log``,
    ``dt_bias``, its norm and the gated norm's scale of one head's
    width; attention: its norm and two head norms; every layer its MLP's
    norm."""
    d = _dims(c)
    e = d["e"]
    mixer = (mixer_matrices(c) + d["taps"] * d["conv_dim"] + 2 * d["hv"]
             + e + d["dv"])
    attention = attention_matrices(c) + e + 2 * c["head_dim"]
    mlp = int(expert_layer_matrices(c, c["num_experts"])) + e
    return (d["linear"] * mixer + d["full"] * attention
            + d["layers"] * mlp + 2 * c["vocab_size"] * e + e)


def scan_macs_per_token(c: Dict, chunk: int = CHUNK) -> Dict[str, float]:
    """Forward multiply-adds a token a mixer of the CHUNKED algebra's
    NECESSARY products (``ray_tpu/ops/gated_delta.py``'s text; whatever
    implements them).  A product over a chunk's ``C x C`` counts its
    triangular half: ``K K^T`` strictly below the diagonal and ``Q K^T``
    with it, once a KEY head; the triangular system solved for ``U`` and
    ``W`` together (``d_k + d_v`` columns, forward substitution: no
    inverse is needed); ``lower(Q K^T * G) V'``; and three ``d_k x d_v``
    products a position a value head (``W S``, ``Q S``, ``K^T V'``)."""
    d = _dims(c)
    below, with_diag = (chunk - 1) / 2, (chunk + 1) / 2
    return {"kk": d["hk"] * below * d["dk"],
            "qk": d["hk"] * with_diag * d["dk"],
            "solve": d["hv"] * below * (d["dk"] + d["dv"]),
            "scores_v": d["hv"] * with_diag * d["dv"],
            "state": 3.0 * d["hv"] * d["dk"] * d["dv"]}


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of the cut model requires, 3 x
    the forward's 2 a multiply-add; recompute (remat) NOT counted.
    Forward: a linear mixer's three projections and the scan's necessary
    products; attention's projections (the output gate's with them), the
    scores and the weighted sum over the VISIBLE pairs; a layer's router,
    its shared expert with its gate and the routed experts a token meets
    HERE on average (``top_k x held / published``); the head.  The
    convolution, the norms and the embedding lookup are not matrix
    products."""
    d = _dims(c)
    mixer = 2 * mixer_matrices(c) + 2 * sum(scan_macs_per_token(c).values())
    pairs = costs_afmoe.visible_pairs(seq, None) / seq
    attention = 2 * attention_matrices(c) + \
        2 * c["num_attention_heads"] * 2 * c["head_dim"] * pairs
    here = (c["num_experts_per_tok"] * c["num_experts"]
            / c["published"]["num_experts"])
    mlp = 2 * expert_layer_matrices(c, here)
    forward = (d["linear"] * mixer + d["full"] * attention
               + d["layers"] * mlp + 2 * c["vocab_size"] * d["e"])
    return 3.0 * forward


def gdn_call_cost(kind: str, batch: int, seq: int, c: Dict,
                  chunk: int = CHUNK, itemsize: int = 2
                  ) -> Dict[str, float]:
    """FLOPs and HBM bytes ONE call of the scan of ``kind`` (``fwd`` |
    ``bwd``) needs over ``batch`` sequences.

    ``fwd``: :func:`scan_macs_per_token`.  ``bwd``: every forward product
    has two gradient products, and the two score matrices are made again
    (``K K^T`` and ``Q K^T``: keeping them would cost more bytes than
    they do operations).  Bytes: ``fwd`` reads ``q``, ``k``, ``v`` and
    the float32 ``g`` and ``beta`` once and writes ``o`` once; ``bwd``
    reads those and ``d o`` and writes ``d q``, ``d k``, ``d v`` and the
    float32 ``d g`` and ``d beta``.  The float32 states a design passes
    from forward to backward are NOT counted: the algorithm could make
    them again."""
    assert kind in ("fwd", "bwd")
    d = _dims(c)
    macs = scan_macs_per_token(c, chunk)
    forward = sum(macs.values())
    per_token = {"fwd": forward,
                 "bwd": 2 * forward + macs["kk"] + macs["qk"]}[kind]
    rows = batch * seq
    narrow, wide, heads = rows * d["key"], rows * d["value"], rows * d["hv"]
    bytes_ = {"fwd": (2 * narrow + 2 * wide) * itemsize + 2 * heads * 4,
              "bwd": (4 * narrow + 4 * wide) * itemsize + 4 * heads * 4}
    return {"flops": 2.0 * rows * per_token, "bytes": float(bytes_[kind])}


def gdn_step_cost(c: Dict, batch: int, seq: int, remat: bool
                  ) -> Dict[str, float]:
    """All scan calls of one train step: per linear layer and per
    sequence a forward (twice under full remat) and one backward."""
    d = _dims(c)
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0, "fwd": 0, "bwd": 0}
    for kind, n in (("fwd", 2 if remat else 1), ("bwd", 1)):
        cost = gdn_call_cost(kind, 1, seq, c)
        times = batch * n * d["linear"]
        out["flops"] += times * cost["flops"]
        out["bytes"] += times * cost["bytes"]
        out["calls"] += times
        out[kind] = times
    return out


def flash_step_cost(c: Dict, batch: int, seq: int, remat: bool
                    ) -> Dict[str, float]:
    """All flash calls of one train step: per full-attention layer and
    per sequence a forward (twice under full remat), one dK/dV and one dQ
    call, causal, no window, ``num_attention_heads`` query heads on
    ``num_key_value_heads`` K/V heads of ``head_dim``: only the visible
    pairs, K and V read once a K/V head
    (``costs_afmoe.flash_call_cost``)."""
    d = _dims(c)
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0}
    for kind, n in (("fwd", 2 if remat else 1), ("dkdv", 1), ("dq", 1)):
        cost = costs_afmoe.flash_call_cost(
            kind, 1, seq, c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], None)
        times = batch * n * d["full"]
        out["flops"] += times * cost["flops"]
        out["bytes"] += times * cost["bytes"]
        out["calls"] += times
    return out


#: (token, choice) pairs that land on the held experts when the router is
#: even, ``tokens x top_k x held / published``: the same keys as Trinity's
expected_live_rows = costs_afmoe.expected_live_rows


def gmm_step_cost(c: Dict, batch: int, seq: int, remat: bool
                  ) -> Dict[str, float]:
    """All grouped products of one train step at the EXPECTED live rows
    (``costs_afmoe.gmm_call_cost`` each): per layer and per sequence
    three projections (gate, up: hidden x width; down: width x hidden),
    each forward (twice under full remat), d lhs and d rhs."""
    d = _dims(c)
    rows = expected_live_rows(c, seq)
    e, w = d["e"], c["moe_intermediate_size"]
    layers = batch * d["layers"]
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0, "rows": batch * rows}
    for k, n in ((e, w), (e, w), (w, e)):
        for kind, times in (("fwd", 2 if remat else 1), ("dlhs", 1),
                            ("drhs", 1)):
            cost = costs_afmoe.gmm_call_cost(kind, rows, k, n,
                                             c["num_experts"])
            out["flops"] += layers * times * cost["flops"]
            out["bytes"] += layers * times * cost["bytes"]
            out["calls"] += layers * times
    return out
