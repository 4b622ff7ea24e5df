"""Operations and bytes the AFMoE cell's algorithms need, from shapes
alone: the benchmark's own copies, as ``costs.py`` is for GPT-2.

``c`` is the configuration file's dict (``benchmarks/configs/
trinity-mini.json``): the source's key names, as run.
"""

from __future__ import annotations

from typing import Dict, Optional


def _dims(c: Dict) -> Dict[str, int]:
    return {"e": c["hidden_size"], "hd": c["num_attention_heads"]
            * c["head_dim"], "kvd": c["num_key_value_heads"] * c["head_dim"],
            "dense": c["num_dense_layers"],
            "expert": c["num_hidden_layers"] - c["num_dense_layers"]}


def attention_params(c: Dict) -> int:
    """q, gate and output projections (hidden x heads*head_dim each), k
    and v (hidden x kv_heads*head_dim), the per-head q/k norm scales and
    the layer's four block norms."""
    d = _dims(c)
    return (3 * d["e"] * d["hd"] + 2 * d["e"] * d["kvd"]
            + 2 * c["head_dim"] + 4 * d["e"])


def num_params(c: Dict) -> int:
    """Parameters of the cut model as held on the chip: ``num_experts``
    is the count HELD, the router keeps its published width."""
    d = _dims(c)
    e = d["e"]
    dense = attention_params(c) + 3 * e * c["intermediate_size"]
    expert = (attention_params(c)
              + 3 * e * c["moe_intermediate_size"] * c["num_shared_experts"]
              + e * c["published"]["num_experts"]
              + c["num_experts"] * 3 * e * c["moe_intermediate_size"])
    return (d["dense"] * dense + d["expert"] * expert
            + 2 * c["vocab_size"] * e + e)


def visible_pairs(seq: int, window: Optional[int]) -> int:
    """(query, key) pairs a causal layer scores: key ``s`` visible to
    query ``t`` iff ``t - window < s <= t``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_windows(c: Dict):
    """The window (or ``None``) of every layer as run."""
    return [c["sliding_window"] if kind == "sliding" else None
            for kind in c["as_run"]["layer_kinds"]]


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of the cut model requires, 3 x
    the forward's 2 a multiply-add; recompute (remat) NOT counted.
    Forward: the attention projections; the scores and the weighted sum
    over the pairs that are VISIBLE (causal, windowed: not the square);
    the dense MLP, or router + shared expert + the routed experts a token
    meets HERE on average (``top_k x held / published``); the head.  The
    embedding lookup is a gather."""
    d = _dims(c)
    e = d["e"]
    proj = 2 * (3 * e * d["hd"] + 2 * e * d["kvd"])
    scores = sum(4 * d["hd"] * visible_pairs(seq, w) / seq
                 for w in layer_windows(c))
    here = (c["num_experts_per_tok"] * c["num_experts"]
            / c["published"]["num_experts"])
    width = c["moe_intermediate_size"]
    expert_mlp = 2 * (e * c["published"]["num_experts"]
                      + 3 * e * width * (c["num_shared_experts"] + here))
    dense_mlp = 2 * 3 * e * c["intermediate_size"]
    forward = ((d["dense"] + d["expert"]) * proj + scores
               + d["dense"] * dense_mlp + d["expert"] * expert_mlp
               + 2 * c["vocab_size"] * e)
    return 3.0 * forward


#: matmul passes over the visible pairs each flash kernel call needs
#: (as ``costs._FLASH_MATMULS``): forward S, O; dK/dV S, dV, dP, dK;
#: dQ S, dP, dQ
_FLASH_MATMULS = {"fwd": 2, "dkdv": 4, "dq": 3}
#: [B,T,H,D]-sized (query-side) and [B,T,Hkv,D]-sized (key-side) arrays
#: each call reads or writes: fwd q o | k v; dK/dV q do | k v dk dv;
#: dQ q do dq | k v
_FLASH_ARRAYS = {"fwd": (2, 2), "dkdv": (2, 4), "dq": (3, 2)}


def flash_call_cost(kind: str, batch: int, seq: int, heads: int,
                    kv_heads: int, head_dim: int, window: Optional[int],
                    itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ONE causal flash call of ``kind`` with
    grouped K/V heads and an optional window: only visible pairs are
    counted (tiles wholly outside the window are skipped by the kernel,
    and the masked part of a straddling tile is no needed work); K and V
    are read once a K/V head, not once a query head."""
    pairs = visible_pairs(seq, window)
    flops = _FLASH_MATMULS[kind] * 2.0 * batch * heads * pairs * head_dim
    big, small = _FLASH_ARRAYS[kind]
    rows = batch * heads * seq
    bytes_ = ((big * rows + small * batch * kv_heads * seq)
              * head_dim * itemsize
              + (1 if kind == "fwd" else 2) * rows * 4)
    return {"flops": flops, "bytes": float(bytes_)}


def flash_step_cost(c: Dict, batch: int, seq: int, remat: bool
                    ) -> Dict[str, float]:
    """All flash calls of one train step: per layer and per sequence
    (the program runs a layer over one sequence at a time) a forward
    (twice under full remat), one dK/dV and one dQ call, each with its
    layer's window."""
    calls = {"fwd": 2 if remat else 1, "dkdv": 1, "dq": 1}
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0}
    for window in layer_windows(c):
        for kind, n in calls.items():
            cost = flash_call_cost(
                kind, 1, seq, c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"], window)
            out["flops"] += batch * n * cost["flops"]
            out["bytes"] += batch * n * cost["bytes"]
            out["calls"] += batch * n
    return out


def gmm_call_cost(kind: str, rows: int, k: int, n: int, experts: int,
                  itemsize: int = 2) -> Dict[str, float]:
    """One grouped product over ``rows`` LIVE rows: ``fwd`` ``[rows, k] x
    [experts, k, n]``, ``dlhs`` the same against the transposed weights,
    ``drhs`` ``[rows, k]^T x [rows, n]`` into ``[experts, k, n]``.  Each
    needs ``2 rows k n`` FLOPs, both row operands once and every held
    expert's matrix once."""
    assert kind in ("fwd", "dlhs", "drhs")
    return {"flops": 2.0 * rows * k * n,
            "bytes": float((rows * (k + n) + experts * k * n) * itemsize)}


def expected_live_rows(c: Dict, tokens: int) -> int:
    """(token, choice) pairs that land on the held experts when the
    router is even: ``tokens x top_k x held / published``."""
    return (tokens * c["num_experts_per_tok"] * c["num_experts"]
            // c["published"]["num_experts"])


def gmm_step_cost(c: Dict, batch: int, seq: int, remat: bool
                  ) -> Dict[str, float]:
    """All grouped products of one train step at the EXPECTED live rows:
    per expert layer and per sequence three projections (gate, up:
    hidden x width; down: width x hidden), each forward (twice under
    full remat), d lhs and d rhs."""
    rows = expected_live_rows(c, seq)
    e, w = c["hidden_size"], c["moe_intermediate_size"]
    layers = batch * (c["num_hidden_layers"] - c["num_dense_layers"])
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0, "rows": batch * rows}
    for k, n in ((e, w), (e, w), (w, e)):
        for kind, times in (("fwd", 2 if remat else 1), ("dlhs", 1),
                            ("drhs", 1)):
            cost = gmm_call_cost(kind, rows, k, n, c["num_experts"])
            out["flops"] += layers * times * cost["flops"]
            out["bytes"] += layers * times * cost["bytes"]
            out["calls"] += layers * times
    return out
