"""``benchmarks/controls/nemotron_h.py`` at a tiny size on the CPU: the
script the builder runs on the chip to show that the cell's two limits
decide something.  The limits are the chip's, so this checks the
script's flow and that each control breaks what it says it breaks, not
who passes."""

import json

import pytest

CONTROLS = ["bf16_params", "lower_precision", "no_carry", "bf16_carry",
            "decay_without_dt", "no_skip", "norm_before_gate",
            "conv_reads_future", "relu_not_squared", "no_shared_expert",
            "rotary_attention"]


@pytest.fixture(scope="module")
def line():
    import jax.numpy as jnp

    from benchmarks.controls import nemotron_h as controls
    from ray_tpu.models import afmoe

    # float32 compute: at width 32 bfloat16's own noise would hide what
    # a control adds; the rounding controls round all the same
    tiny = dict(vocab_size=256, max_seq_len=64, num_layers=2, embed_dim=32,
                num_heads=4, num_kv_heads=2, head_dim=16, ssm_heads=8,
                ssm_head_dim=8, ssm_groups=2, ssm_state=16, chunk=16,
                expert_dim=24, shared_dim=48, num_experts=8, top_k=2,
                experts_held=(2, 4), dtype=jnp.float32)
    arch = dict(top_k=2, first_held=2, ssm_heads=8, ssm_groups=2,
                ssm_state=16, head_dim=16)
    out = []
    with pytest.MonkeyPatch.context() as m:
        m.setattr(afmoe, "BLOCK_ROWS", 8)
        m.setattr("builtins.print", lambda *a, **k: out.append(a[0])
                  if not k.get("file") else None)
        controls.main(["--seeds", "1"], rehearse={
            "config_args": tiny, "batch": 2,
            "ref_kw": {"arch": arch, "query_block": 16, "token_chunk": 32,
                       "scan_segment": 16}})
    return json.loads(out[-1])


def test_the_sound_program_is_reported_beside_its_limits(line):
    assert {"loss_err", "grad_err", "grad_err_own_routing", "loss_rtol",
            "grad_rtol", "topk_flips_per_layer", "landed_share_per_layer",
            "flip_score_gap_max_per_layer", "sound", "caught"} <= set(line)
    assert line["loss_err"] < 1e-5 and line["grad_err"] < 1e-4
    assert len(line["topk_flips_per_layer"]) == 2
    assert set(line["caught"]) == set(CONTROLS)
    # 4 of 8 experts held
    assert all(0.2 < s < 0.8 for s in line["landed_share_per_layer"])


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_reads_worse_than_the_sound_program(line, control):
    # float32 against float32 the sound program reads 1e-7
    assert line[control]["grad_err"] > 100 * line["grad_err"]
