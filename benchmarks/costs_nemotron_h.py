"""Operations and bytes the Nemotron-H (Nemotron-3-Nano-30B-A3B) cell's
algorithms need, from shapes alone: the benchmark's own copies, as
``costs_afmoe.py`` and ``costs_deepseek_v3.py`` are for the two routed
cells before it.

``c`` is the configuration file's dict (``benchmarks/configs/
nemotron-3-nano-30b-a3b.json``): the source's key names, as run; the
pattern as run is ``c["as_run"]["pattern"]``.
"""

from __future__ import annotations

from typing import Dict

from benchmarks import costs_afmoe


def _dims(c: Dict) -> Dict[str, int]:
    heads, dim = c["mamba_num_heads"], c["mamba_head_dim"]
    groups, state = c["n_groups"], c["ssm_state_size"]
    pattern = c["as_run"]["pattern"]
    return {"e": c["hidden_size"], "heads": heads, "dim": dim,
            "groups": groups, "state": state, "inner": heads * dim,
            "conv_dim": heads * dim + 2 * groups * state,
            "taps": c["conv_kernel"], "chunk": c["chunk_size"],
            "q": c["num_attention_heads"] * c["head_dim"],
            "kv": c["num_key_value_heads"] * c["head_dim"],
            "mixers": pattern.count("M"), "experts": pattern.count("E"),
            "attention": pattern.count("*")}


def mixer_matrices(c: Dict) -> int:
    """``W_in`` (hidden x (z | xs B C | dt)) and ``W_out``."""
    d = _dims(c)
    return d["e"] * (d["inner"] + d["conv_dim"] + d["heads"]) \
        + d["inner"] * d["e"]


def attention_matrices(c: Dict) -> int:
    d = _dims(c)
    return 2 * d["e"] * d["q"] + 2 * d["e"] * d["kv"]


def num_params(c: Dict) -> int:
    """Parameters of the cut model as held on the chip:
    ``n_routed_experts`` is the count HELD, the router keeps its
    published width.  A mixer besides its matrices: the convolution and
    its bias, ``dt_bias``, ``A_log``, ``D``, its norm and the gated
    norm's scale."""
    d = _dims(c)
    e = d["e"]
    mixer = (mixer_matrices(c) + (d["taps"] + 1) * d["conv_dim"]
             + 3 * d["heads"] + e + d["inner"])
    attention = attention_matrices(c) + e
    expert = (e * c["published"]["n_routed_experts"]
              + 2 * e * c["moe_shared_expert_intermediate_size"] + e
              + c["n_routed_experts"] * 2 * e * c["moe_intermediate_size"])
    return (d["mixers"] * mixer + d["attention"] * attention
            + d["experts"] * expert + 2 * c["vocab_size"] * e + e)


def _half(chunk: int) -> float:
    """(t, s) pairs with ``s <= t`` a position of a chunk meets on
    average: the causal half of the chunk's ``Q x Q``, diagonal in."""
    return (chunk + 1) / 2


def scan_flops_per_token(c: Dict) -> Dict[str, float]:
    """Forward multiply-adds x 2 a token a mixer of the chunked scan's
    NECESSARY products: ``C B^T`` over the causal half of a chunk once a
    group; its masked, decayed product with ``xs`` a head; the chunk's
    closing state and the read-out of the incoming one (``P x N`` a head
    a position each)."""
    d = _dims(c)
    half = _half(d["chunk"])
    return {"cb": 2.0 * d["groups"] * half * d["state"],
            "scores_x": 2.0 * d["heads"] * half * d["dim"],
            "states": 2.0 * d["heads"] * d["dim"] * d["state"],
            "read_out": 2.0 * d["heads"] * d["dim"] * d["state"]}


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of the cut model requires, 3 x
    the forward's 2 a multiply-add; recompute (remat) NOT counted.
    Forward: a mixer's two projections and the scan's necessary products;
    attention's four projections, the scores and the weighted sum over
    the VISIBLE pairs; an expert layer's router, its shared expert and
    the routed experts a token meets HERE on average (``top_k x held /
    published``), two matrices each; the head.  The convolution, the
    norms and the embedding lookup are not matrix products."""
    d = _dims(c)
    e = d["e"]
    mixer = 2 * mixer_matrices(c) + sum(scan_flops_per_token(c).values())
    pairs = costs_afmoe.visible_pairs(seq, None) / seq
    attention = 2 * attention_matrices(c) + \
        2 * c["num_attention_heads"] * 2 * c["head_dim"] * pairs
    here = (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["published"]["n_routed_experts"])
    expert = 2 * (e * c["published"]["n_routed_experts"]
                  + 2 * e * c["moe_shared_expert_intermediate_size"]
                  + 2 * e * c["moe_intermediate_size"] * here)
    forward = (d["mixers"] * mixer + d["attention"] * attention
               + d["experts"] * expert + 2 * c["vocab_size"] * e)
    return 3.0 * forward


def ssd_call_cost(kind: str, batch: int, seq: int, heads: int, dim: int,
                  groups: int, state: int, chunk: int,
                  itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes ONE call of the chunked scan's kernel of
    ``kind`` (``fwd`` | ``bwd``) needs over ``batch`` sequences.

    A product over the ``Q x Q`` of a chunk counts its causal half.
    ``fwd``: ``C B^T`` once a group; a head's scores times ``xs``; the
    closing state and the read-out (``P x N`` a head a position each).
    ``bwd`` recomputes ``C B^T`` and makes ``d(C B^T) B`` and ``d(C
    B^T)^T C`` once a group; ``dy xs^T`` and ``scores^T dy`` a head; and
    four ``P x N`` products a head a position (``d C`` of the read-out,
    the carry's cotangent, ``d xs`` and ``d B`` of the closing state).
    Bytes: ``fwd`` reads ``xs``, ``B``, ``C`` and the float32 ``dt``
    once and writes ``y`` once; ``bwd`` reads those and ``dy`` and writes
    ``d xs``, ``d B``, ``d C`` and the float32 ``d dt`` and ``d cum``.
    The float32 states a design passes from forward to backward are NOT
    counted: the algorithm could recompute them."""
    assert kind in ("fwd", "bwd")
    rows = batch * seq
    half = _half(chunk)
    group_sq = rows * groups * half * state      # a Q x Q product a group
    head_sq = rows * heads * half * dim          # a Q x Q product a head
    head_pn = rows * heads * dim * state
    macs = {"fwd": group_sq + head_sq + 2 * head_pn,
            "bwd": 3 * group_sq + 2 * head_sq + 4 * head_pn}[kind]
    wide, narrow = rows * heads * dim, rows * groups * state
    bytes_ = {"fwd": (2 * wide + 2 * narrow) * itemsize + rows * heads * 4,
              "bwd": (3 * wide + 4 * narrow) * itemsize
              + 3 * rows * heads * 4}[kind]
    return {"flops": 2.0 * macs, "bytes": float(bytes_)}


def ssd_step_cost(c: Dict, batch: int, seq: int, remat: bool
                  ) -> Dict[str, float]:
    """All scan calls of one train step: per mixer and per sequence a
    forward (twice under full remat) and one backward call."""
    d = _dims(c)
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0, "fwd": 0, "bwd": 0}
    for kind, n in (("fwd", 2 if remat else 1), ("bwd", 1)):
        cost = ssd_call_cost(kind, 1, seq, d["heads"], d["dim"],
                             d["groups"], d["state"], d["chunk"])
        times = batch * n * d["mixers"]
        out["flops"] += times * cost["flops"]
        out["bytes"] += times * cost["bytes"]
        out["calls"] += times
        out[kind] = times
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
    sequence TWO projections (up: hidden x width; down: width x hidden;
    the width as published, 1856, whatever tile the kernel lays over
    it), each forward (twice under full remat), d lhs and d rhs."""
    d = _dims(c)
    rows = expected_live_rows(c, seq)
    e, w = d["e"], c["moe_intermediate_size"]
    layers = batch * d["experts"]
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0, "rows": batch * rows}
    for k, n in ((e, w), (w, e)):
        for kind, times in (("fwd", 2 if remat else 1), ("dlhs", 1),
                            ("drhs", 1)):
            cost = costs_afmoe.gmm_call_cost(kind, rows, k, n,
                                             c["n_routed_experts"])
            out["flops"] += layers * times * cost["flops"]
            out["bytes"] += layers * times * cost["bytes"]
            out["calls"] += layers * times
    return out
