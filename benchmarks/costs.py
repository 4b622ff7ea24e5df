"""Operations and bytes an algorithm needs, from shapes alone.  The
benchmark's own copies: a later PR may change the program, not the
yardstick.
"""

from __future__ import annotations

from typing import Dict


def gpt2_num_params(sizes: Dict[str, int]) -> int:
    """Parameters of GPT-2 with a tied head: wte + wpe + per layer
    12*E*E weights and 13*E biases/LN + the final LN."""
    e, v = sizes["n_embd"], sizes["vocab_size"]
    per_layer = 12 * e * e + 13 * e
    return (v * e + sizes["n_positions"] * e
            + sizes["n_layer"] * per_layer + 2 * e)


def gpt2_train_flops_per_token(sizes: Dict[str, int], seq: int) -> float:
    """Forward + backward FLOPs a token requires (PaLM / nanoGPT
    convention): 6*N over all parameters plus the attention term
    12*L*E*T.  Recomputed operations (remat) are NOT counted."""
    attn = 12 * sizes["n_layer"] * sizes["n_embd"] * seq
    return 6.0 * gpt2_num_params(sizes) + attn


#: matmul passes of [T,T,D] each causal flash kernel call needs:
#: forward S=QK^T, O=PV; dK/dV recomputes S and makes dV, dP, dK;
#: dQ recomputes S and dP and makes dQ
_FLASH_MATMULS = {"fwd": 2, "dkdv": 4, "dq": 3}
#: [B,T,H,D] arrays each call reads or writes (q, k, v, o / do, and the
#: gradients it produces); the per-row lse/delta vectors are added apart
_FLASH_ARRAYS = {"fwd": 4, "dkdv": 6, "dq": 5}


def flash_call_cost(kind: str, batch: int, seq: int, heads: int,
                    head_dim: int, causal: bool = True,
                    itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ONE flash-attention kernel call of
    ``kind`` ("fwd", "dkdv" or "dq") over ``batch`` sequences.  A causal
    kernel needs half the [T,T] tiles."""
    full = 2.0 * batch * heads * seq * seq * head_dim
    flops = _FLASH_MATMULS[kind] * full * (0.5 if causal else 1.0)
    rows = batch * heads * seq
    bytes_ = (_FLASH_ARRAYS[kind] * rows * head_dim * itemsize
              + (1 if kind == "fwd" else 2) * rows * 4)
    return {"flops": flops, "bytes": float(bytes_)}


def flash_step_cost(layers: int, batch: int, seq: int, heads: int,
                    head_dim: int, remat: bool) -> Dict[str, float]:
    """All flash calls of one train step on one device: per layer a
    forward (twice under full remat: the backward recomputes it), one
    dK/dV and one dQ call."""
    calls = {"fwd": 2 if remat else 1, "dkdv": 1, "dq": 1}
    out = {"flops": 0.0, "bytes": 0.0, "calls": 0}
    for kind, n in calls.items():
        c = flash_call_cost(kind, batch, seq, heads, head_dim)
        out["flops"] += layers * n * c["flops"]
        out["bytes"] += layers * n * c["bytes"]
        out["calls"] += layers * n
    return out


def roofline_seconds(flops: float, bytes_: float,
                     peak: Dict[str, float]) -> Dict[str, object]:
    """Least time the chip could take, and which bound holds."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
