"""``benchmarks/controls/deepseek_v3.py`` at a tiny size on the CPU: the
script the builder runs on the chip to show that the cell's two limits
decide something.  The limits are the chip's, so this checks the
script's flow and that each control breaks what it says it breaks, not
who passes."""

import json

import pytest

CONTROLS = ["bf16_params", "lower_precision", "scale_128", "no_rope_on_key",
            "rotate_half", "no_latent_norm", "no_route_scale",
            "one_shared_expert"]


@pytest.fixture(scope="module")
def line():
    import jax.numpy as jnp

    from benchmarks.controls import deepseek_v3 as controls
    from ray_tpu.models import afmoe

    # float32 compute: at width 32 bfloat16's own noise (0.05) would
    # hide what a control adds; the rounding controls round all the same
    tiny = dict(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4,
                qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                kv_lora_rank=32, embed_dim=32, dense_dim=64, expert_dim=16,
                num_experts=8, top_k=2, experts_held=(2, 4),
                dtype=jnp.float32)
    arch = dict(top_k=2, first_held=2)
    out = []
    with pytest.MonkeyPatch.context() as m:
        m.setattr(afmoe, "BLOCK_ROWS", 8)
        m.setattr("builtins.print", lambda *a, **k: out.append(a[0])
                  if not k.get("file") else None)
        controls.main(["--seeds", "1"], rehearse={
            "config_args": tiny, "batch": 2,
            "ref_kw": {"arch": arch, "query_block": 16, "token_chunk": 32}})
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
    # float32 against float32 the sound program reads 1e-7; at N(0, 0.02)
    # weights of width 32 attention is all but uniform, so the controls
    # that touch the scores alone read 1e-4, the others 1e-3 .. 1
    assert line[control]["grad_err"] > 100 * line["grad_err"]
