"""``benchmarks/controls/qwen3_next.py`` at a tiny size on the CPU: the
script the builder runs on the chip to show that the cell's two limits
decide something.  The limits are the chip's, so this checks the
script's flow and that each control breaks what it says it breaks, not
who passes."""

import json

import pytest

CONTROLS = ["bf16_params", "lower_precision", "no_carry", "bf16_carry",
            "no_decay", "no_correction", "beta_one", "no_l2_norm",
            "gate_before_norm", "conv_reads_future", "no_output_gate",
            "rotary_all", "norm_scale_w", "shared_ungated",
            "sigmoid_router"]


@pytest.fixture(scope="module")
def line():
    import jax.numpy as jnp

    from benchmarks.controls import qwen3_next as controls
    from ray_tpu.models import afmoe

    # float32 compute: at width 32 bfloat16's own noise would hide what
    # a control adds; the rounding controls round all the same
    tiny = dict(vocab_size=256, max_seq_len=64, num_layers=2, embed_dim=32,
                num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
                lin_key_heads=2, lin_value_heads=4, lin_key_dim=8,
                lin_value_dim=8, chunk=16, expert_dim=24, shared_dim=24,
                num_experts=8, top_k=2, experts_held=(2, 4),
                dtype=jnp.float32)
    arch = dict(top_k=2, first_held=2, head_dim=16, rotary_dim=4,
                key_heads=2, value_heads=4)
    out = []
    rehearse = {"config_args": tiny, "batch": 2,
                "ref_kw": {"arch": arch, "query_block": 16,
                           "token_chunk": 32, "scan_segment": 16}}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(afmoe, "BLOCK_ROWS", 8)
        m.setattr("builtins.print", lambda *a, **k: out.append(a[0])
                  if not k.get("file") else None)
        controls.main(["--seeds", "1", "--skip-scan"], rehearse=rehearse)
        scans = controls.scan_readings(rehearse)
    return dict(json.loads(out[-1]), scan_error=scans)


def test_the_sound_program_is_reported_beside_its_limits(line):
    assert {"loss_err", "grad_err", "grad_err_own_routing", "loss_rtol",
            "grad_rtol", "topk_flips_per_layer", "landed_share_per_layer",
            "imbalance_per_layer", "live_tiles_per_layer",
            "flip_score_gap_max_per_layer", "sound", "caught"} <= set(line)
    assert line["loss_err"] < 1e-5 and line["grad_err"] < 1e-4
    # every layer of L L F has its routed experts
    assert len(line["topk_flips_per_layer"]) == 3
    assert set(line["caught"]) == set(CONTROLS)
    # 4 of 8 experts held
    assert all(0.2 < s < 0.8 for s in line["landed_share_per_layer"])


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_reads_worse_than_the_sound_program(line, control):
    # float32 against float32 the sound program reads 1e-7; at the
    # source's initial weights (norm weights zero) a norm's scale ``w``
    # for ``1 + w`` silences every layer, which the LOSS reads; a carry
    # rounded to bfloat16 over the four chunks of 16 that a sequence of
    # 64 has is for the scan's own probe to read
    worse = line[control]["grad_err"] > 100 * line["grad_err"]
    if control == "bf16_carry":
        worse = line["scan_error"][control] > 3 * line["scan_error"]["sound"]
    assert worse or line[control]["loss_err"] > 100 * line["loss_err"]


def test_the_scan_s_probe_reads_every_control_that_stands_in_for_the_scan(
        line):
    scans = dict(line["scan_error"])
    routes = scans.pop("route_error")
    # the router's own probe: every control that is another configuration
    assert set(routes) == {"sound", "lower_precision", "rotary_all",
                           "sigmoid_router"}
    assert routes["sound"] < 1e-6 and routes["rotary_all"] < 1e-6
    assert routes["sigmoid_router"] > 1e-2
    assert routes["lower_precision"] > 1e-4
    assert set(scans) == {"sound", "lower_precision", "no_carry",
                          "bf16_carry", "no_decay", "no_correction",
                          "beta_one"}
    assert scans["sound"] < 1e-6
    for name in ("no_carry", "no_decay", "no_correction", "beta_one",
                 "lower_precision"):
        assert scans[name] > 1e-4, name
