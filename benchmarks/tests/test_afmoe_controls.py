"""``benchmarks/controls/afmoe.py`` at a tiny size on the CPU: the
script the builder runs on the chip to show that the cell's two limits
decide something.  The limits are the chip's, so this checks the
script's flow and what each control does, not who passes."""

import json

import pytest


@pytest.fixture(scope="module")
def line():
    from benchmarks.controls import afmoe as controls
    from ray_tpu.models import afmoe

    tiny = dict(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=16, embed_dim=32, dense_dim=64,
                expert_dim=32, num_experts=8, top_k=2, experts_held=(2, 4),
                window=24, expert_layer_start=2)
    arch = dict(window=24, top_k=2, first_held=2, expert_layer_start=2)
    out = []
    with pytest.MonkeyPatch.context() as m:
        # row tiles of 8: at this size the short buffers of ``drops``
        # would hide in one tile of 256
        m.setattr(afmoe, "BLOCK_ROWS", 8)
        m.setattr("builtins.print", lambda *a, **k: out.append(a[0])
                  if not k.get("file") else None)
        controls.main(["--seeds", "1"], rehearse={
            "config_args": tiny, "batch": 2,
            "ref_kw": {"arch": arch, "query_block": 16, "token_chunk": 32}})
    return json.loads(out[-1])


def test_the_sound_program_is_reported_beside_its_limits(line):
    assert {"loss_err", "grad_err", "grad_err_own_routing", "loss_rtol",
            "grad_rtol", "topk_flips_per_layer",
            "flip_score_gap_max_per_layer", "sound", "caught"} <= set(line)
    assert line["loss_err"] < 1e-3 and line["grad_err"] < 0.05
    # a flipped choice is a near tie of the reference's scores
    assert max(line["flip_score_gap_max_per_layer"]) < 0.05
    assert len(line["topk_flips_per_layer"]) == 2


@pytest.mark.parametrize("control", ["window_short", "no_route_scale",
                                     "drops"])
def test_a_control_reads_worse_than_the_sound_program(line, control):
    assert line[control]["grad_err"] > 1.5 * line["grad_err"]
    assert set(line["caught"]) == {"window_short", "no_route_scale",
                                   "drops", "lower_precision"}
    assert line[control]["misrouted_share"] >= line["misrouted_share"]


def test_a_bfloat16_router_is_refused_at_the_published_router_width():
    """128 experts, 8 a token (the cell's router; everything else at the
    rehearsal's sizes): float32 routers on the bfloat16 stream misroute
    next to no token, a router computed in bfloat16 a quarter of them,
    and the loss the harness differentiates is then the constant 0."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmarks.reference import afmoe as ref, afmoe_paired
    from ray_tpu.models import afmoe

    base = afmoe.AFMoEConfig.trinity_mini_share(
        remat="full", vocab_size=256, max_seq_len=128, num_layers=2,
        num_heads=2, embed_dim=64)
    shapes = meta.unbox(jax.eval_shape(lambda: afmoe.AFMoE(
        base).init_params(jax.random.PRNGKey(1), batch=2)))
    params = ref.init_like(shapes, jax.random.PRNGKey(1))
    tokens = np.random.default_rng(7).integers(0, 256, (2, 128),
                                               dtype=np.int32)
    share = {}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        model = afmoe.AFMoE(dataclasses.replace(base, router_dtype=dtype))
        loss, share[name] = afmoe_paired.program_loss(
            model, params, tokens, with_misrouted=True)
        assert (float(loss) == 0.0) == (name == "bfloat16")
    assert float(share["float32"]) < afmoe_paired.MISROUTED_MAX / 2
    assert float(share["bfloat16"]) > afmoe_paired.MISROUTED_MAX * 2
