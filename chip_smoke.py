"""Chip smoke: the main path, once, on the accelerator.

``python chip_smoke.py`` drives ``ray_tpu.init()`` ->
``JaxTrainer(..., ScalingConfig(num_workers=1, tpus_per_worker=1)).fit()``
training GPT-2 124M at its published widths (12 layers x 12 heads x 768,
vocabulary 50257, sequence 1024, bf16 compute / f32 params, flash
attention kernels, chunked LM head, AdamW) for a few steps on a repeated
batch made from ``--seed``.  The gang worker leases the chip from the
raylet, opens it, compiles, steps, reports loss and a checkpoint; a
second, short run in the same call shows the persistent compile cache
hit.  ``--chips 4`` runs ONLY the FSDP-over-four phase and the one-device
run it is compared with (one worker, ``tpus_per_worker=4``).

One process per chip: THIS process never initialises a jax backend —
everything it knows about the device came back from the worker through
``session.report``.  It exits non-zero, within a deadline of its own, if
the worker saw anything but a TPU, if the gang never got its lease, if
``result.error`` is set, or if any check fails; nothing continues on the
CPU.  The last line of standard output is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}`` and nothing is
written after it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import threading
import time
import traceback

#: the driver allows 1200 s; leave room to say why before it cuts us
DEADLINE_S = 1080.0
#: |loss(FSDP over 4) - loss(one device)| allowed at every step: same
#: init, batch and optimizer; bf16 matmuls reduce in a different order
FSDP_LOSS_TOL = 5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


# ---------------------------------------------------------------------------
# train loops — these run in the gang worker that leased the chip(s)
# ---------------------------------------------------------------------------

def _device_report(jax) -> dict:
    import ray_tpu

    dev = jax.devices()[0]
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count(), "backend": jax.default_backend(),
        "pid": os.getpid(), "leased_tpu_ids": ray_tpu.get_tpu_ids(),
        "TPU_VISIBLE_CHIPS": os.environ.get("TPU_VISIBLE_CHIPS"),
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }


def _count_cache_events(jax) -> dict:
    counts = {"hits": 0, "misses": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def _model_and_batch(config, jax):
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import GPT2, GPT2Config

    cfg = (GPT2Config.tiny(dtype=jnp.float32) if config["model"] == "tiny"
           else GPT2Config.gpt2_small())
    model = GPT2(cfg)
    tokens = np.random.default_rng(config["seed"]).integers(
        0, cfg.vocab_size, (config["batch"], cfg.max_seq_len),
        dtype=np.int32)
    boxed = model.init_params(jax.random.PRNGKey(config["seed"]), batch=1)
    tx = optax.adamw(6e-4, weight_decay=0.01)
    return cfg, model, tx, boxed, tokens


def _compile_and_step(jax, step, params, opt_state, tokens, n_steps):
    """AOT-compile ``step`` (timed; goes through the persistent cache),
    then take ``n_steps`` on the repeated batch, each timed to
    ``block_until_ready``.  Losses come back as Python floats."""
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, tokens).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    losses, step_s = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tokens)
        loss.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return params, {
        "compile_s": compile_s, "losses": losses, "step_s": step_s,
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }


def train_loop(config):
    """One chip: GPT-2 through the shared train step, a few steps."""
    import jax

    from flax.core import meta

    from ray_tpu.models.gpt2 import make_train_step
    from ray_tpu.train import Checkpoint, session

    cache = _count_cache_events(jax)
    device = _device_report(jax)
    for line in range(config["chatter"]):
        print(f"worker chatter line {line}", flush=True)
    cfg, model, tx, boxed, tokens = _model_and_batch(config, jax)
    params = meta.unbox(boxed)
    opt_state = tx.init(params)
    params, run = _compile_and_step(
        jax, make_train_step(model, tx), params, opt_state,
        jax.numpy.asarray(tokens), config["steps"])
    stats = jax.devices()[0].memory_stats() or {}
    session.report(
        {"device": device, "cache": cache, **run,
         "params": cfg.num_params(), "batch": config["batch"],
         "seq": cfg.max_seq_len,
         "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
         "bytes_limit": stats.get("bytes_limit")},
        checkpoint=Checkpoint.from_pytree(params)
        if config["checkpoint"] else None)


def fsdp_loop(config):
    """Four chips, one process: GPT-2 placed with FSDP_RULES over a
    ``fsdp=4`` mesh, then the same init/batch/optimizer on one device
    of the same worker."""
    import jax
    from flax.core import meta
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt2 import make_train_step
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.parallel.mesh import set_global_mesh
    from ray_tpu.parallel.sharding import FSDP_RULES, place_flax_params
    from ray_tpu.train import session

    device = _device_report(jax)
    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise RuntimeError(f"the worker leased 4 chips and sees {device}")
    cfg, model, tx, boxed, tokens = _model_and_batch(config, jax)
    # a host copy of the init: the donated steps delete device buffers,
    # and placing on the mesh may alias the ones already on device 0
    host_params = jax.device_get(meta.unbox(boxed))

    mesh = build_mesh(MeshConfig(fsdp=4), devices=devices)
    set_global_mesh(mesh)  # the flash kernels run per batch shard
    params, _ = place_flax_params(boxed, FSDP_RULES, mesh)
    batch = jax.device_put(
        tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
    opt_state = tx.init(params)
    leaves = jax.tree.leaves(params)
    placement = {
        "param_leaves": len(leaves),
        "param_leaves_on_4_devices": sum(
            len(x.sharding.device_set) == 4 for x in leaves),
        "param_leaves_split": sum(
            not x.sharding.is_fully_replicated for x in leaves),
        "batch_devices": len(batch.sharding.device_set),
        "batch_shard_shape": list(batch.addressable_shards[0].data.shape),
        "opt_leaves_on_4_devices": sum(
            len(x.sharding.device_set) == 4
            for x in jax.tree.leaves(opt_state) if x.ndim),
    }
    params, sharded = _compile_and_step(
        jax, make_train_step(model, tx), params, opt_state, batch,
        config["steps"])
    placement["bytes_in_use_per_device"] = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    placement["peak_bytes_per_device"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    del params, opt_state, batch
    set_global_mesh(None)

    params = jax.device_put(host_params, devices[0])
    opt_state = tx.init(params)
    _, single = _compile_and_step(
        jax, make_train_step(model, tx), params, opt_state,
        jax.device_put(tokens, devices[0]), config["steps"])
    session.report({"device": device, "placement": placement,
                    "sharded": sharded, "single": single,
                    "batch": config["batch"], "seq": cfg.max_seq_len})


# ---------------------------------------------------------------------------
# the parent: starts the runtime, owns no device
# ---------------------------------------------------------------------------

def _fit(loop, loop_config, tpus: int, storage: str):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        loop, train_loop_config=loop_config,
        scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=1,
                                     tpus_per_worker=tpus),
        run_config=RunConfig(storage_path=storage)).fit()
    check(result.error is None, f"train loop failed:\n{result.error}")
    check(bool(result.metrics), "the gang finished without a report")
    return result


def _check_run(run: dict, what: str, want_kernels: bool) -> None:
    import math

    losses = run["losses"]
    check(all(math.isfinite(x) for x in losses),
          f"{what}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{what}: loss did not fall on a repeated batch: {losses}")
    if want_kernels:
        check(run["tpu_custom_calls"] > 0,
              f"{what}: no tpu_custom_call in the compiled step — the "
              f"flash kernels did not compile into it")


def one_chip(args, expect: str, tpus: int, storage: str) -> dict:
    loop_config = {"model": args.model, "seed": args.seed,
                   "batch": args.batch, "steps": args.steps,
                   "chatter": args.chatter, "checkpoint": True}
    result = _fit(train_loop, loop_config, tpus, storage)
    m = result.metrics
    device = m["device"]
    log(f"worker device: {json.dumps(device)}")
    check(device["platform"] == expect,
          f"the worker that leased the chip opened {device}")
    _check_run(m, "train", want_kernels=expect == "tpu")
    check(result.checkpoint is not None, "no checkpoint came back")
    steady = m["step_s"][1:]
    log(f"model: {m['params']} params, batch {m['batch']} x seq {m['seq']}")
    log(f"compile (first run): {m['compile_s']:.2f} s, cache {m['cache']}")
    log(f"loss: {[round(x, 4) for x in m['losses']]}")
    log(f"step seconds (block_until_ready): first {m['step_s'][0]:.4f}, "
        f"then median {statistics.median(steady):.4f} -> "
        f"{m['batch'] * m['seq'] / statistics.median(steady):.0f} tokens/s")
    log(f"peak bytes in use: {m['peak_bytes_in_use']} of "
        f"{m['bytes_limit']}; tpu_custom_call in step: "
        f"{m['tpu_custom_calls']}")

    # same program again, in a new worker process: the persistent cache
    again = _fit(train_loop, {**loop_config, "steps": 2, "chatter": 0,
                              "checkpoint": False}, tpus, storage).metrics
    log(f"compile (second run, new process): {again['compile_s']:.2f} s, "
        f"cache {again['cache']}, dir {again['device']['cache_dir']}")
    check(again["device"]["pid"] != device["pid"],
          "the second run reused the first run's worker")
    check(again["cache"]["hits"] > 0 or expect != "tpu",
          f"the second run did not hit the persistent compile cache at "
          f"{again['device']['cache_dir']}: {again['cache']}")
    check(abs(again["losses"][0] - m["losses"][0]) < 1e-3,
          f"same seed, different first loss: {again['losses'][0]} vs "
          f"{m['losses'][0]}")
    return device


def four_chips(args, expect: str, tpus: int, storage: str) -> dict:
    m = _fit(fsdp_loop, {"model": args.model, "seed": args.seed,
                         "batch": args.batch, "steps": args.steps},
             tpus, storage).metrics
    device, place = m["device"], m["placement"]
    log(f"worker device: {json.dumps(device)}")
    check(device["platform"] == expect and device["count"] >= 4,
          f"the worker that leased four chips opened {device}")
    log(f"placement: {json.dumps(place)}")
    check(place["param_leaves_on_4_devices"] == place["param_leaves"]
          and place["batch_devices"] == 4,
          f"parameters or batch are not on four devices: {place}")
    check(place["param_leaves_split"] > 0
          and place["batch_shard_shape"][0] * 4 == m["batch"],
          f"nothing is actually split over the mesh: {place}")
    if expect == "tpu":
        check(all(b and b > 0 for b in place["bytes_in_use_per_device"]),
              f"a device holds nothing: {place}")
    for what in ("sharded", "single"):
        _check_run(m[what], what, want_kernels=expect == "tpu")
        log(f"{what}: compile {m[what]['compile_s']:.2f} s, step median "
            f"{statistics.median(m[what]['step_s'][1:]):.4f} s, loss "
            f"{[round(x, 4) for x in m[what]['losses']]}, "
            f"tpu_custom_call {m[what]['tpu_custom_calls']}")
    gap = max(abs(a - b) for a, b in zip(m["sharded"]["losses"],
                                         m["single"]["losses"]))
    log(f"max |loss(fsdp=4) - loss(one device)| = {gap:.5f} "
        f"(tolerance {FSDP_LOSS_TOL})")
    check(gap <= FSDP_LOSS_TOL,
          f"FSDP over four disagrees with one device by {gap}")
    return device


def _worker_log_tails(session_dir: str, limit: int = 3000) -> None:
    """On failure, say what the workers said (stderr only)."""
    import glob

    for path in sorted(glob.glob(os.path.join(session_dir, "logs", "*.err"))):
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - limit))
            tail = f.read().decode(errors="replace").strip()
        if tail:
            print(f"--- {path}\n{tail}", file=sys.stderr)


def main(argv=None, *, rehearse: bool = False) -> None:
    """``rehearse`` (tests only, not on the command line): the same
    control flow at a tiny size on the CPU — a worker that leases no
    chip — to prove the output discipline, never a device result."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps", type=int, default=8)
    parser.add_argument("--batch", type=int, default=32)
    args = parser.parse_args(argv)
    args.model, args.chatter = ("tiny", 200) if rehearse else ("small", 0)
    expect, tpus = ("cpu", 0) if rehearse else ("tpu", args.chips)

    def out_of_time():
        print(f"[chip_smoke] FAILED: not done after {DEADLINE_S:.0f} s",
              file=sys.stderr, flush=True)
        os._exit(4)  # the head and its workers die with this process

    watchdog = threading.Timer(DEADLINE_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()

    t_start = time.time()
    session_dir = None
    import ray_tpu
    try:
        from ray_tpu.core import native

        native.build()  # once, before any daemon or worker needs it
        import jax  # imported, never initialised here
        from jax._src import xla_bridge

        # worker output stays in the session's log files: nothing but
        # this process writes to this stdout
        info = ray_tpu.init(_system_config={"log_to_driver": False})
        session_dir = info["session_dir"]
        have = ray_tpu.cluster_resources().get("TPU", 0)
        log(f"cluster: {have:g} TPU chip(s) detected, "
            f"{ray_tpu.cluster_resources().get('CPU', 0):g} CPUs")
        check(have >= tpus,
              f"this host has {have:g} TPU chip(s) (/dev/accel*, "
              f"/dev/vfio/<n>), {tpus} needed: no accelerator, no result")
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
            phase = one_chip if args.chips == 1 else four_chips
            device = phase(args, expect, tpus, storage)
        ray_tpu.shutdown()
        check(not xla_bridge.backends_are_initialized(),
              "the parent initialised a jax backend: it would hold the chip")
    except BaseException as e:  # noqa: BLE001 — reported, then fatal
        traceback.print_exc()
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        if session_dir:
            _worker_log_tails(session_dir)
        ray_tpu.shutdown()
        sys.exit(1)
    watchdog.cancel()
    log(f"done in {time.time() - t_start:.1f} s")
    sys.stderr.flush()
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    os._exit(0)  # nothing runs after the last line: no atexit, no echo


if __name__ == "__main__":
    main()
