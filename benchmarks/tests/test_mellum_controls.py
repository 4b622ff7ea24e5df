"""``benchmarks/controls/mellum.py`` at a tiny size on four CPU devices:
the script the builder runs on the four chips to show that the cell's
two limits decide something.  The limits are the chip's, so this checks
the script's flow (the mesh, the preset's placement, the split batch)
and that each control breaks what it says it breaks, not who passes."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CONTROLS = ["full_as_sliding", "no_attention_factor", "window_2048",
            "sigmoid_router", "not_renormalised", "no_scatter_sum",
            "bf16_router", "bf16_head_logits", "lower_precision"]


#: the script's own ``main`` in a process of its own, which has the four
#: CPU devices the mesh takes (these tests' process has one).  float32
#: compute: at width 32 bfloat16's own noise would hide what a control
#: adds; the rounding controls round all the same
CODE = """
import jax.numpy as jnp
from benchmarks.controls import mellum as controls
from ray_tpu.models import afmoe
afmoe.BLOCK_ROWS = 8
tiny = dict(vocab_size=256, max_seq_len=64, num_layers=2, layer_stop=4,
            num_heads=4, num_kv_heads=2, head_dim=16, embed_dim=32,
            expert_dim=16, num_experts=8, top_k=2, experts_held=(0, 8),
            window=24, yarn_original_max=32, routed_tokens=32,
            dtype=jnp.float32)
arch = dict(window=24, top_k=2, layer_stop=4, yarn=dict(
    rope_theta=500000.0, factor=16.0, original_max_position_embeddings=32,
    beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782))
controls.main(["--seeds", "1"], rehearse={
    "config_args": tiny, "batch": 8,
    "ref_kw": {"arch": arch, "query_block": 16, "token_chunk": 32}})
"""


@pytest.fixture(scope="module")
def line():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CODE], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=900)
    assert proc.stdout.strip(), proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_the_sound_program_is_reported_beside_its_limits(line):
    assert {"loss_err", "grad_err", "grad_err_unjudged", "misrouted_share",
            "misrouted_by_gap", "grad_err_own_routing", "loss_rtol",
            "grad_rtol", "routing_gap", "misrouted_max",
            "arrived_share_per_layer_per_chip",
            "exchange_bytes_a_forward", "sound", "caught"} <= set(line)
    # float32 against float32 the routing is the reference's: the error
    # as judged is the arithmetic's
    assert line["grad_err"] == line["grad_err_unjudged"]
    assert line["misrouted_share"] == line["misrouted_by_gap"][2] == 0.0
    assert line["loss_err"] < 1e-5 and line["grad_err"] < 1e-4
    assert set(line["caught"]) == set(CONTROLS)
    shares = line["arrived_share_per_layer_per_chip"]
    assert len(shares) == 2 and all(len(s) == 4 for s in shares)
    assert all(sum(s) == pytest.approx(1.0) for s in shares)


#: a float32 head takes no logits dtype (``ops/fused.py`` ``_chunk_nll``:
#: the bfloat16 store is of the bfloat16 product), so at this test's
#: float32 compute that control is the sound program
@pytest.mark.parametrize("control", [c for c in CONTROLS
                                     if c != "bf16_head_logits"])
def test_a_control_reads_worse_than_the_sound_program(line, control):
    # float32 against float32 the sound program reads 1e-6; a window of
    # 48 of 64 and the attention factor move scores that are all but
    # uniform at these weights, the others read 1e-3 .. 1
    worse = max(line[control]["grad_err"] / line["grad_err"],
                line[control]["loss_err"] / max(line["loss_err"], 1e-9))
    assert worse > 30


@pytest.mark.parametrize("control", ["bf16_router", "lower_precision"])
def test_a_misrouted_control_reads_one_as_judged(line, control):
    """More tokens misrouted than ``MISROUTED_MAX``: the loss the harness
    differentiates is 0, and the error is that of no gradient at all."""
    assert line[control]["misrouted_share"] > line["misrouted_max"]
    assert line[control]["grad_err"] == pytest.approx(1.0, abs=1e-6)
    assert 0 < line[control]["grad_err_unjudged"] < 0.01


def test_the_chip_s_readings_stand_either_side_of_the_committed_limits():
    """``controls/mellum.readings.jsonl`` is what ``python3 benchmarks/
    controls/mellum.py --seeds 2`` read on four v5e chips with the
    weights as the cell draws them (PR 51, call 7; the verdict and the
    limits of that day taken off the lines).  Under the limits AS
    COMMITTED that run's verdict is the one the script exits 0 on: the
    sound program inside, every control but the ``UNSEEN`` outside one;
    and each limit this PR brings has room on both sides of it."""
    from benchmarks.controls import mellum as controls
    from benchmarks.reference import mellum as ref
    from benchmarks.reference import mellum_paired as paired

    with open(os.path.join(REPO, "benchmarks", "controls",
                           "mellum.readings.jsonl")) as f:
        lines = [json.loads(text) for text in f]
    assert len(lines) == 2
    for line in lines:
        assert line["routing_gap"] == paired.ROUTING_GAP
        sound, caught = controls.verdict(
            line, ref.LOSS_RTOL, ref.GRAD_RTOL, paired.MISROUTED_MAX)
        assert sound and set(caught) == set(CONTROLS)
        assert {name for name, hit in caught.items() if not hit} \
            == controls.UNSEEN

    def largest(*path):
        reads = lines
        for key in path[:-1]:
            reads = [one[key] for one in reads]
        return (max(one[path[-1]] for one in reads),
                min(one[path[-1]] for one in reads))

    program, _ = largest("grad_err_unjudged")
    _, lowered = largest("lower_precision", "grad_err_unjudged")
    assert 1.3 * program < ref.GRAD_RTOL < lowered / 1.3
    astray, _ = largest("misrouted_share")
    _, rounded_router = largest("bf16_router", "misrouted_share")
    assert 2 * astray < paired.MISROUTED_MAX < rounded_router / 2
    assert 3 * largest("loss_err")[0] < ref.LOSS_RTOL
