"""``benchmarks/controls/ouro.py`` at a tiny size on the CPU: the script
the builder runs on the chip to show that the cell's two limits decide
something.  The limits are the chip's, so this checks the script's flow
and that each control breaks what it says it breaks, not who passes."""

import json

import pytest

CONTROLS = ["three_passes_not_four", "no_norm_between_passes",
            "post_norms_dropped", "exits_weighted_evenly", "gate_detached",
            "no_entropy_term", "bf16_head_logits", "bf16_exit_distribution",
            "lower_precision"]


@pytest.fixture(scope="module")
def line():
    import jax.numpy as jnp

    from benchmarks.controls import ouro as controls

    # float32 compute: at width 64 bfloat16's own noise would hide what
    # a control adds; the rounding controls round all the same
    tiny = dict(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4,
                head_dim=16, embed_dim=64, mlp_dim=96, dtype=jnp.float32)
    out = []
    with pytest.MonkeyPatch.context() as m:
        m.setattr("builtins.print", lambda *a, **k: out.append(a[0])
                  if not k.get("file") else None)
        controls.main(["--seeds", "1"], rehearse={
            "config_args": tiny, "batch": 2,
            "ref_kw": {"query_block": 16, "token_chunk": 32}})
    return json.loads(out[-1])


def test_the_sound_program_is_reported_beside_its_limits(line):
    assert {"loss_err", "grad_err", "loss_rtol", "grad_rtol", "ref_loss",
            "loss", "sound", "caught"} <= set(line)
    assert line["loss_err"] < 1e-5 and line["grad_err"] < 1e-4
    assert set(line["caught"]) == set(CONTROLS)


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_reads_worse_than_the_sound_program(line, control):
    if control == "bf16_head_logits":
        # the head rounds its logits on the bfloat16 path alone, which a
        # float32 rehearsal does not take: the chip's run reads it
        assert line[control]["grad_err"] == pytest.approx(
            line["grad_err"], rel=0.5)
        return
    worse = max(line[control]["grad_err"] / line["grad_err"],
                line[control]["loss_err"] / line["loss_err"])
    assert worse > 100


def test_a_detached_gate_shows_in_the_gate_s_gradient_alone(line):
    # the loss is the sound program's to the digit; the whole tree's
    # distance would hide a gate's gradient, its own does not
    assert line["gate_detached"]["loss_err"] == line["loss_err"]
    assert line["gate_detached"]["grad_err"] == pytest.approx(1.0)
