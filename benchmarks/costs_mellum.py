"""Operations and bytes the Mellum cell's algorithms need, from shapes
alone: the benchmark's own copies, as ``costs_afmoe.py`` is for
Trinity-Mini.

``c`` is the configuration file's dict (``benchmarks/configs/
mellum2-12b-a2.5b.json``): the source's key names, as run.  The cell
runs on the ``c["chips"]`` chips that share each layer; what a function
counts for "a chip" is one chip's share of the step: its own sequences'
attention and head, the (token, choice) pairs of the GROUP's tokens that
ARRIVED on its experts (a quarter at an even router, as many as its own
tokens chose), and what crosses its links.
"""

from __future__ import annotations

from typing import Dict

from benchmarks import costs_afmoe


def _dims(c: Dict) -> Dict[str, int]:
    return {"e": c["hidden_size"],
            "hd": c["num_attention_heads"] * c["head_dim"],
            "kvd": c["num_key_value_heads"] * c["head_dim"],
            "w": c["moe_intermediate_size"], "n": c["num_experts"],
            "k": c["num_experts_per_tok"], "layers": c["num_hidden_layers"],
            "chips": c["chips"], "held": c["as_run"]["experts_held"][1],
            "piece": c["as_run"]["routed_tokens"]}


def attention_params(c: Dict) -> int:
    """q and output projections (hidden x heads*head_dim each), k and v
    (hidden x kv_heads*head_dim); no gate, no q/k norm."""
    d = _dims(c)
    return 2 * d["e"] * d["hd"] + 2 * d["e"] * d["kvd"]


def num_params(c: Dict) -> int:
    """Parameters of the stage as the four chips hold them together:
    every layer whole (its two norms, the router, all experts), the
    embedding, the head and the final norm."""
    d = _dims(c)
    layer = (attention_params(c) + 2 * d["e"] + d["e"] * d["n"]
             + d["n"] * 3 * d["e"] * d["w"])
    return d["layers"] * layer + 2 * c["vocab_size"] * d["e"] + d["e"]


def layer_windows(c: Dict):
    """The window (or ``None``) of every layer as run."""
    return [c["sliding_window"] if kind == "sliding" else None
            for kind in c["as_run"]["layer_kinds"]]


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of the stage requires, 3 x the
    forward's 2 a multiply-add; recompute (remat) NOT counted.  Forward:
    the attention projections; the scores and the weighted sum over the
    pairs that are VISIBLE (causal, windowed); the router and the
    ``top_k`` experts a token meets (all of them are here: somewhere in
    the group); the head.  The embedding lookup is a gather and the
    exchange moves bytes."""
    d = _dims(c)
    e = d["e"]
    proj = 2 * attention_params(c)
    scores = sum(4 * d["hd"] * costs_afmoe.visible_pairs(seq, w) / seq
                 for w in layer_windows(c))
    moe = 2 * (e * d["n"] + 3 * e * d["w"] * d["k"])
    forward = d["layers"] * (proj + moe) + scores + 2 * c["vocab_size"] * e
    return 3.0 * forward


def flash_step_cost(c: Dict, batch: int, seq: int, remat: bool
                    ) -> Dict[str, float]:
    """All flash calls of one train step ON ONE CHIP (``batch``: its
    sequences): per layer and per sequence a forward (twice under full
    remat), one dK/dV and one dQ call, each with its layer's window."""
    calls = {"fwd": 2 if remat else 1, "dkdv": 1, "dq": 1}
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0}
    for window in layer_windows(c):
        for kind, n in calls.items():
            cost = costs_afmoe.flash_call_cost(
                kind, 1, seq, c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"], window)
            out["flops"] += batch * n * cost["flops"]
            out["bytes"] += batch * n * cost["bytes"]
            out["calls"] += batch * n
    return out


def routed_calls(c: Dict, batch: int, seq: int) -> int:
    """Calls of a routed layer on one chip a forward pass: a layer, a
    sequence of the chip and a piece of ``routed_tokens`` of it."""
    d = _dims(c)
    return d["layers"] * batch * -(-seq // min(d["piece"], seq))


def arrived_rows(c: Dict, seq: int) -> int:
    """(token, choice) pairs of ONE CALL's group tokens that arrive on a
    chip's experts when the router is even: ``chips x piece x top_k x
    held / published``."""
    d = _dims(c)
    return d["chips"] * min(d["piece"], seq) * d["k"] * d["held"] // d["n"]


def gmm_step_cost(c: Dict, batch: int, seq: int, remat: bool
                  ) -> Dict[str, float]:
    """All grouped products of one train step on one chip at the
    EXPECTED rows that arrived: a call of a routed layer three
    projections (gate, up: hidden x width; down: width x hidden), each
    forward (twice under full remat), d lhs and d rhs, over the chip's
    16 experts."""
    d = _dims(c)
    rows = arrived_rows(c, seq)
    calls = routed_calls(c, batch, seq)
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0, "rows": calls * rows}
    for k, n in ((d["e"], d["w"]), (d["e"], d["w"]), (d["w"], d["e"])):
        for kind, times in (("fwd", 2 if remat else 1), ("dlhs", 1),
                            ("drhs", 1)):
            cost = costs_afmoe.gmm_call_cost(kind, rows, k, n, d["held"])
            out["flops"] += calls * times * cost["flops"]
            out["bytes"] += calls * times * cost["bytes"]
            out["calls"] += calls * times
    return out


def exchange_call_bytes(c: Dict, seq: int, itemsize: int = 2
                        ) -> Dict[str, int]:
    """Bytes ONE chip receives in the gather of one call (the other
    chips' rows in the compute dtype, their ``top_k`` int32 choices and
    float32 weights) and sends in its scatter (its part of the other
    chips' rows)."""
    d = _dims(c)
    others = (d["chips"] - 1) * min(d["piece"], seq)
    return {"gather": others * (d["e"] * itemsize + d["k"] * 8),
            "scatter": others * d["e"] * itemsize}


def exchange_step_bytes(c: Dict, batch: int, seq: int, remat: bool
                        ) -> Dict[str, float]:
    """What crosses one chip's links for the exchange in one train step:
    a call's forward a gather and a scatter (twice under full remat);
    its backward the transposes, a gather of the parts' cotangents (the
    scatter's size) and a scatter of the rows' and the weights' (the
    gather's size less the choices, which carry no gradient)."""
    d = _dims(c)
    one = exchange_call_bytes(c, seq)
    calls = routed_calls(c, batch, seq)
    forward = one["gather"] + one["scatter"]
    choices = (d["chips"] - 1) * min(d["piece"], seq) * d["k"] * 4
    backward = one["scatter"] + one["gather"] - choices
    passes = 2 if remat else 1
    return {"bytes": float(calls * (passes * forward + backward)),
            "calls": calls}
