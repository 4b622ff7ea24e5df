"""The chunked scan of a selective state space (``ray_tpu/ops/ssd.py``)
against the time-step recurrence it stands for, on the CPU with the
kernels in interpret mode: forward and all six gradients at 1, 2 and 5
chunks, float32 tight and bfloat16 inputs within a band; a length that
is not whole chunks refused; and the carry's float32, shown by a case a
bfloat16 carry fails."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.core import telemetry
from ray_tpu.ops import ssd as scan

NAMES = ("xs", "dt", "A", "B", "C", "D")


def _inputs(chunks, chunk=16, heads=8, dim=8, groups=2, state=16,
            dtype=jnp.float32, batch=2, seed=0):
    t = chunks * chunk
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    xs = jax.random.normal(ks[0], (batch, t, heads, dim), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, t, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0., maxval=1.5))
    b = (0.5 * jax.random.normal(ks[3], (batch, t, groups, state))
         ).astype(dtype)
    c = (0.5 * jax.random.normal(ks[4], (batch, t, groups, state))
         ).astype(dtype)
    d = jax.random.normal(ks[5], (heads,))
    return xs, dt, a, b, c, d


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def _loss(fn, weight):
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)


#: float32: the same arithmetic in another order.  bfloat16 inputs: the
#: products round their operands to 8 bits (the scores too), the decays,
#: the state and the carry stay float32: the band is the rounding of the
#: operands, 2^-8, a few times over
@pytest.mark.parametrize("chunks", [1, 2, 5])
@pytest.mark.parametrize("path", ["kernels", "kernels_two_to_a_slab",
                                  "einsum"])
@pytest.mark.parametrize("dtype,fwd_tol,grad_tol", [
    (jnp.float32, 2e-6, 2e-5), (jnp.bfloat16, 8e-3, 3e-2)])
def test_scan_matches_the_recurrence_forward_and_all_six_gradients(
        chunks, path, dtype, fwd_tol, grad_tol):
    # the cell's slabs: heads of 64, two to the 128 lanes (the others
    # pack a group's four heads of 8 into one slab)
    wide = {"heads": 4, "dim": 64} if path == "kernels_two_to_a_slab" else {}
    args = _inputs(chunks, dtype=dtype, **wide)
    fn = (lambda *a: scan.ssd_einsum(*a, chunk=16)) if path == "einsum" \
        else (lambda *a: scan.ssd(*a, chunk=16, interpret=True))
    want = scan.ssd_recurrence(*args)
    got = fn(*args)
    assert got.dtype == dtype and got.shape == args[0].shape
    assert _rel(got, want) <= fwd_tol
    weight = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = jax.grad(_loss(fn, weight), argnums=range(6))(*args)
    wants = jax.grad(_loss(scan.ssd_recurrence, weight),
                     argnums=range(6))(*args)
    for name, g, w, arg in zip(NAMES, grads, wants, args):
        assert g.dtype == arg.dtype and g.shape == arg.shape, name
        assert _rel(g, w) <= grad_tol, name


def test_a_length_that_is_not_whole_chunks_is_refused_and_says_so():
    xs, dt, a, b, c, d = _inputs(2)
    cut = lambda v: v[:, :24]  # noqa: E731
    with pytest.raises(ValueError, match="24 positions is not whole "
                                         "chunks of 16"):
        scan.ssd(cut(xs), cut(dt), a, cut(b), cut(c), d, chunk=16,
                 interpret=True)


def test_heads_have_to_split_over_the_groups():
    args = _inputs(1, heads=7)
    with pytest.raises(ValueError, match="7 heads do not split over 2"):
        scan.ssd(*args, chunk=16, interpret=True)


def _slow_decay_inputs():
    """Decays that stay near 1 over 4 chunks and inputs of one sign: the
    state grows chunk after chunk, so what the carry loses of it shows."""
    xs, dt, _, b, c, d = _inputs(4, batch=1, seed=3)
    return (jnp.abs(xs), 0.02 * jnp.ones_like(dt), -0.05 * jnp.ones((8,)),
            jnp.abs(b), jnp.abs(c), jnp.zeros_like(d))


def test_the_carry_is_float32_and_a_bfloat16_carry_would_fail(monkeypatch):
    args = _slow_decay_inputs()
    want = scan.ssd_recurrence(*args)
    last = slice(-16, None)     # the last chunk reads three carries
    kernels = scan.ssd(*args, chunk=16, interpret=True)
    assert _rel(kernels[:, last], want[:, last]) <= 2e-6
    assert _rel(scan.ssd_einsum(*args, chunk=16)[:, last],
                want[:, last]) <= 2e-6

    real = scan._carry

    def rounded(states, decay, reverse=False):
        def step(s, inp):
            new = inp[1][..., None, None] * s + inp[0]
            return new.astype(jnp.bfloat16).astype(jnp.float32), s
        swap = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
        return swap(jax.lax.scan(step, jnp.zeros_like(states[:, 0]),
                                 (swap(states), swap(decay)))[1])

    assert _rel(rounded(jnp.ones((1, 3, 8, 4, 4)), jnp.ones((1, 3, 8))),
                real(jnp.ones((1, 3, 8, 4, 4)), jnp.ones((1, 3, 8)))) == 0
    monkeypatch.setattr(scan, "_carry", rounded)
    lost = scan.ssd_einsum(*args, chunk=16)
    assert _rel(lost[:, last], want[:, last]) > 100 * 2e-6


def test_the_plan_span_says_what_scan_was_compiled():
    args = _inputs(2)
    telemetry.drain_spans("test")
    jax.eval_shape(lambda *a: scan.ssd(*a, chunk=16, interpret=True), *args)
    rows = [r for r in telemetry.drain_spans("test")
            if r["name"] == "ssd.plan"]
    assert len(rows) == 1 and rows[0]["cat"] == "ops"
    assert rows[0]["args"] == {
        "heads": 8, "head_dim": 8, "groups": 2, "state": 16, "chunk": 16,
        "seq": 32, "chunks": 2, "heads_a_step": 4, "carry": "kernel"}


def _shapes_outside_kernels(jaxpr):
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                yield tuple(v.aval.shape)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _shapes_outside_kernels(sub)


def test_b_and_c_are_read_by_group_and_no_q_by_q_array_leaves_a_kernel():
    """Every array outside the two ``pallas_call``s of forward and
    backward, at 4 heads of 4 in 2 groups of state 16, chunks of 8: none
    is ``chunk x chunk`` (the decayed scores stay in the kernels), and
    none holds a state-wide row a HEAD (B and C stay 2 groups wide)."""
    args = _inputs(2, chunk=8, heads=4, dim=4, groups=2, state=16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: scan.ssd(*a, chunk=8, interpret=False).astype(
            jnp.float32).sum(), argnums=range(6)))(*args)
    assert str(jaxpr).count("pallas_call") == 2
    shapes = set(_shapes_outside_kernels(jaxpr.jaxpr))
    assert (2, 16, 2, 16) in shapes                 # B, C as given
    # (the one [8, 8] array is the triangle of ones that sums ``dt A``)
    assert not [s for s in shapes if s[-2:] == (8, 8) and len(s) > 2]
    assert not [s for s in shapes if s[-2:] == (4, 16)]
    # and the einsum formulation does have both, which is its cost
    plain = set(_shapes_outside_kernels(jax.make_jaxpr(
        lambda *a: scan.ssd_einsum(*a, chunk=8))(*args).jaxpr))
    assert [s for s in plain if 8 in s[2:4] and s.count(8) >= 2]
