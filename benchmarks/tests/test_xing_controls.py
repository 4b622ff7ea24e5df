"""``benchmarks/controls/xing.py`` at a tiny size on the CPU: the script
the builder runs on the chip to show that the cell's two limits decide
something.  The limits are the chip's, so this checks the script's flow
and that each control breaks what it says it breaks, not who passes."""

import json

import pytest

CONTROLS = ["lower_precision", "coef_bf16", "sinkhorn_4", "row_then_column",
            "post_gain_1", "no_lane_norm", "first_lane_only", "scale_192",
            "plain_rope", "no_query_norm", "no_route_scale"]


@pytest.fixture(scope="module")
def line():
    import jax.numpy as jnp

    from benchmarks.controls import xing as controls
    from ray_tpu.models import afmoe

    # float32 compute: at width 32 bfloat16's own noise would hide what
    # a control adds; the rounding controls round all the same
    tiny = dict(vocab_size=256, max_seq_len=32, num_layers=1, num_heads=4,
                num_dense_layers=1, qk_nope_dim=16, qk_rope_dim=8,
                v_head_dim=16, kv_lora_rank=32, q_lora_rank=16,
                embed_dim=32, dense_dim=64, expert_dim=16, num_experts=8,
                top_k=2, experts_held=(2, 4), dtype=jnp.float32)
    arch = dict(top_k=2, first_held=2)
    out = []
    with pytest.MonkeyPatch.context() as m:
        m.setattr(afmoe, "BLOCK_ROWS", 8)
        m.setattr("builtins.print", lambda *a, **k: out.append(a[0])
                  if not k.get("file") else None)
        controls.main(["--seeds", "1", "--skip-loss"], rehearse={
            "config_args": tiny, "batch": 2,
            "ref_kw": {"arch": arch, "query_block": 16, "token_chunk": 16}})
    return json.loads(out[-1])


def test_the_sound_program_is_reported_beside_its_limits(line):
    assert {"grad_err", "grad_err_own_routing", "loss_rtol", "grad_rtol",
            "misrouted_share", "sound", "caught"} <= set(line)
    assert line["grad_err"] < 1e-4
    assert set(line["caught"]) == set(CONTROLS)


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_reads_worse_than_the_sound_program(line, control):
    # float32 against float32 the sound program reads 1e-7; a control
    # reads 1e-5 .. 1.  All but one: where twenty Sinkhorn steps have
    # CONVERGED the matrix is the one doubly-stochastic scaling of its
    # start, whichever of rows and columns a step divides first, so
    # ``row_then_column`` differs by what the iteration has left undone
    # and by where ``hc_eps`` enters, and reads as the sound program here
    # (the chip's reading and what it means: PERF.md section 6, PR 54)
    factor = 0.5 if control == "row_then_column" else 20
    assert line[control]["grad_err"] > factor * line["grad_err"]
