"""Device-plane observability suite (ISSUE 18 /
docs/observability.md#device-plane): XLA compile accounting in
lockstep with the jit cache (bucketed shapes are NOT storms,
steady-state compile count is zero), StepMonitor phase splits
telescoping to step wall time within the 5% gate, RankSkewWindow
straggler naming, and the RecompileStorm / GangStraggler alert
lifecycles on a fake-clock MetricsHistory (fires within 3 evaluation
ticks, names the rank, resolves once the condition clears)."""

import time

import numpy as np
import pytest

from ray_tpu.core import device_telemetry as dt
from ray_tpu.core.metrics_history import (MetricsHistory,
                                          default_alert_rules,
                                          default_recording_rules)


@pytest.fixture(autouse=True)
def _isolate_compile_registry():
    dt.reset_for_tests()
    yield
    dt.reset_for_tests()


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

def test_instrument_step_counts_first_and_shape_miss():
    """The wrapper keys its seen-set the way jit keys its executable
    cache: arrays by (shape, dtype), scalars by type.  First signature
    is `first`, each later new one a `shape_miss`; repeats are free."""
    calls = []

    def fn(x, scale=1):
        calls.append(x.shape)
        return x * scale

    step = dt.instrument_step(fn, name="t.step")
    assert dt.is_instrumented(step)
    assert not dt.is_instrumented(fn)
    assert step.__wrapped__ is fn

    a4 = np.zeros((4,), dtype=np.float32)
    a8 = np.zeros((8,), dtype=np.float32)
    step(a4)
    step(a4)                       # same signature: no compile
    step(np.ones((4,), dtype=np.float32))  # values differ, shape same
    assert dt.compile_count("t.step") == 1
    step(a8)                       # new shape: recompile
    step(a4.astype(np.int32))      # new dtype: recompile
    step(a4, scale=2)              # default -> explicit kwarg: retrace
    step(a4, scale=2)              # same kwarg signature: free
    step(a4, scale=2.5)            # int -> float: jit would retrace
    st = dt.compile_stats()["t.step"]
    assert st["first"] == 1
    assert st["shape_miss"] == 4
    assert st["total"] == dt.compile_count("t.step") == 5
    assert st["seconds"] >= 0.0
    assert len(calls) == 8         # every call still executed


def test_compile_accounting_tracks_toy_decoder_trace_count():
    """Lockstep cross-check against the jit cache itself: the toy
    decoder's traced-function side effect (`trace_count`) fires once
    per actual XLA trace, and the wrapper must count exactly that —
    one compile per padding bucket at warmup, then ZERO at steady
    state no matter how many requests run through the same buckets."""
    dec = __import__("ray_tpu.serve.toy_decoder",
                     fromlist=["ToyDecoder"]).ToyDecoder(dim=8)
    for i in range(3):             # prompts spanning the 8-bucket
        dec.generate_unbatched({"prompt": [2, 3, 4], "max_new_tokens": 3})
    warm = dt.compile_count("toy_decoder.step")
    assert warm == dec.trace_count >= 1
    # steady state: same buckets, more traffic -> zero new compiles
    for i in range(5):
        dec.generate_unbatched({"prompt": [5, 6], "max_new_tokens": 3})
    assert dt.compile_count("toy_decoder.step") == warm == dec.trace_count
    # a genuinely new bucket IS a (single) recompile, not a storm
    dec.generate_unbatched({"prompt": list(range(2, 12)),
                            "max_new_tokens": 3})
    assert dt.compile_count("toy_decoder.step") == dec.trace_count
    assert dt.compile_stats()["toy_decoder.step"]["shape_miss"] == \
        dec.trace_count - 1


# ---------------------------------------------------------------------------
# step-time attribution
# ---------------------------------------------------------------------------

def test_step_monitor_phases_telescope_to_wall_time():
    """The acceptance gate: data_wait + host + device + sync recorded
    per step must sum to the step's measured wall time within 5%."""
    mon = dt.StepMonitor("train", name="t", flops_per_token=100.0,
                         peak_flops=1000.0)
    wall_total = 0.0
    for _ in range(5):
        t_prev = time.time()
        # phases long enough that a loaded machine's oversleep (a few
        # ms beside five busy test workers) cannot reorder them
        time.sleep(0.020)                       # the input-pipeline wait
        span = mon.step(data_wait_s=time.time() - t_prev)
        time.sleep(0.015)                       # host dispatch
        span.dispatched()
        time.sleep(0.060)                       # device compute
        span.device_done()
        time.sleep(0.010)                       # sync / bookkeeping
        span.done(tokens=50.0)
        wall_total += time.time() - t_prev
    st = mon.stats()
    assert st["steps"] == 5
    phase_sum = sum(st["phase_s"].values())
    assert phase_sum == pytest.approx(st["wall_s"])
    assert abs(phase_sum - wall_total) / wall_total <= 0.05
    # derived signals are consistent with the recorded phases
    assert st["tokens"] == 250.0
    assert st["goodput_per_s"] == pytest.approx(250.0 / phase_sum,
                                                rel=0.01)
    assert st["mfu"] == pytest.approx(
        st["goodput_per_s"] * 100.0 / 1000.0)
    assert 0.0 < st["data_wait_frac"] < 1.0
    assert 0.0 < st["device_frac"] < 1.0
    assert st["device_frac"] > st["data_wait_frac"]  # 60 ms vs 20 ms


def test_step_monitor_attributes_device_seconds_to_thread():
    """record_step folds device time into the thread-local pool the
    worker brackets around task bodies (the analyze exec split)."""
    base = dt.device_seconds()
    mon = dt.StepMonitor("rl", name="t2")
    mon.record_step(host_s=0.01, device_s=0.25, tokens=1.0)
    mon.record_step(device_s=0.5)
    assert dt.device_seconds() - base == pytest.approx(0.75)


def test_step_monitor_partial_bracket_degrades_cleanly():
    """A span finished without dispatched()/device_done() stamps must
    still telescope: the whole interval lands in one phase instead of
    going missing."""
    mon = dt.StepMonitor("serve", name="t3", deployment="d")
    span = mon.step()
    time.sleep(0.005)
    span.done(requests=2.0)        # no dispatched/device_done
    st = mon.stats()
    assert st["steps"] == 1 and st["requests"] == 2.0
    assert st["phase_s"]["device"] == 0.0
    assert st["phase_s"]["sync"] == 0.0
    assert sum(st["phase_s"].values()) == pytest.approx(
        st["phase_s"]["host"]) and st["phase_s"]["host"] >= 0.005


# ---------------------------------------------------------------------------
# gang rank skew
# ---------------------------------------------------------------------------

def test_rank_skew_window_names_straggler():
    w = dt.RankSkewWindow(world=3, window=8)
    # fewer than two reporting ranks: no skew verdict yet
    w.record({0: 0.01})
    assert w.snapshot() == {"rank_step_s": [0.01, 0.0, 0.0],
                            "skew_s": 0.0, "straggler": 0}
    for _ in range(8):
        w.record({0: 0.010, 1: 0.012, 2: 0.110})
    snap = w.snapshot()
    assert snap["straggler"] == 2
    assert snap["skew_s"] == pytest.approx(0.1)
    assert snap["rank_step_s"][2] == pytest.approx(0.110)
    # the window is rolling: a recovered rank 2 drains the skew
    for _ in range(8):
        w.record({0: 0.010, 1: 0.012, 2: 0.011})
    assert w.snapshot()["skew_s"] < 0.01
    # out-of-range ranks are ignored, not crashes
    w.record({7: 1.0, -1: 1.0})
    assert len(w.snapshot()["rank_step_s"]) == 3


# ---------------------------------------------------------------------------
# alert lifecycles (fake clock, real default rules)
# ---------------------------------------------------------------------------

def _history(interval=1.0, window=240.0):
    return MetricsHistory(interval, window,
                          recording_rules=default_recording_rules(interval),
                          alert_rules=default_alert_rules(interval))


def _counter_rec(name, value, tags=()):
    return {(name, tags): {"name": name, "type": "counter",
                           "tags": dict(tags), "value": value}}


def _gauge_rec(name, value, tags=()):
    return {(name, tags): {"name": name, "type": "gauge",
                           "tags": dict(tags), "value": value}}


def test_recompile_storm_fires_within_three_ticks_then_resolves():
    """An unbucketed-shape barrage pushes device:compile_rate over the
    0.5/s threshold -> RecompileStorm fires within 3 evaluation ticks;
    once shapes stabilize (counter flat) the rate window drains and
    the alert resolves through hysteresis."""
    h = _history()
    tags = (("fn", "engine.step"), ("reason", "shape_miss"))
    # quiet boot: no compile series at all -> no derived signal, no
    # false pending state
    h.sample({}, now=99.0)
    assert h.evaluate(now=99.0) == []
    # barrage: 100 recompiles land in one tick
    h.sample(_counter_rec("ray_tpu_xla_compiles_total", 0.0, tags),
             now=100.0)
    h.sample(_counter_rec("ray_tpu_xla_compiles_total", 100.0, tags),
             now=101.0)
    transitions = list(h.evaluate(now=101.0))
    ticks_to_fire = 1
    t = 101.0
    while not any(tr["rule"] == "RecompileStorm" and tr["to"] == "firing"
                  for tr in transitions):
        t += 1.0
        ticks_to_fire += 1
        assert ticks_to_fire <= 3, "RecompileStorm missed the 3-tick gate"
        h.sample(_counter_rec("ray_tpu_xla_compiles_total", 100.0, tags),
                 now=t)
        transitions += h.evaluate(now=t)
    assert any(a["rule"] == "RecompileStorm" for a in h.firing())
    # shapes stabilize: the counter goes flat, the 60s rate window
    # slides past the burst, and the alert must RESOLVE (not linger)
    resolved = False
    while t < 180.0 and not resolved:
        t += 1.0
        h.sample(_counter_rec("ray_tpu_xla_compiles_total", 100.0, tags),
                 now=t)
        resolved = any(tr["rule"] == "RecompileStorm"
                       and tr["to"] == "resolved"
                       for tr in h.evaluate(now=t))
    assert resolved
    assert not any(a["rule"] == "RecompileStorm" for a in h.firing())


def test_gang_straggler_alert_names_rank_then_resolves():
    """Persistent rank skew over 50ms fires GangStraggler within 3
    evaluation ticks WITH the straggling rank in its tags; skew
    draining below threshold resolves it."""
    h = _history()
    tags = (("deployment", "gang2"), ("straggler", "1"))
    h.sample(_gauge_rec("ray_tpu_gang_rank_skew_seconds", 0.12, tags),
             now=100.0)
    transitions = list(h.evaluate(now=100.0))
    ticks_to_fire = 1
    t = 100.0
    while not any(tr["rule"] == "GangStraggler" and tr["to"] == "firing"
                  for tr in transitions):
        t += 1.0
        ticks_to_fire += 1
        assert ticks_to_fire <= 3, "GangStraggler missed the 3-tick gate"
        h.sample(_gauge_rec("ray_tpu_gang_rank_skew_seconds", 0.12,
                            tags), now=t)
        transitions += h.evaluate(now=t)
    firing = [a for a in h.firing() if a["rule"] == "GangStraggler"]
    assert firing and firing[0]["tags"] == {"deployment": "gang2",
                                            "straggler": "1"}
    # the slow rank recovers: sustained sub-threshold skew resolves
    resolved = False
    while t < 130.0 and not resolved:
        t += 1.0
        h.sample(_gauge_rec("ray_tpu_gang_rank_skew_seconds", 0.001,
                            tags), now=t)
        resolved = any(tr["rule"] == "GangStraggler"
                       and tr["to"] == "resolved"
                       for tr in h.evaluate(now=t))
    assert resolved
    assert not any(a["rule"] == "GangStraggler" for a in h.firing())
