"""The ``train`` kind of cell: ``ray_tpu.init()`` -> ``JaxTrainer.fit()``
-> a gang worker that leases its chips, opens them and runs
``worker_loop`` below.

Two halves.  ``run`` is the driver side: it lives in the benchmark's
parent process, which never initialises a jax backend (one process per
chip), starts the runtime, watches the checkpoint directory, and turns
the worker's final report into the record ``benchmarks/run.py`` prints
from.  ``worker_loop`` is the train loop itself; everything about the
device is learned there and comes back through ``session.report``.

A configuration names the program's entry points as dotted paths
(``module:attr``), a traffic file names the job's shape, so neither
needs an edit here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib
import math
import os
import queue
import shutil
import statistics
import sys
import tempfile
import threading
import time

import numpy
from typing import Any, Dict, List, Optional

#: the driver allows a run 360 s and a compiling run 1200 s: say why
#: before it cuts us
DEADLINE_S = 1100.0
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCRATCH = os.path.join(ROOT, ".bench_scratch")


class BenchFailure(RuntimeError):
    pass


def resolve(path: str) -> Any:
    """``"pkg.mod:attr.attr"`` -> the object."""
    module, _, attrs = path.partition(":")
    obj = importlib.import_module(module)
    for attr in attrs.split(".") if attrs else ():
        obj = getattr(obj, attr)
    return obj


# ---------------------------------------------------------------------------
# the worker: holds the chips
# ---------------------------------------------------------------------------

class _Spans:
    """Host spans on two clocks at once: ``time.time()`` rows for the
    metrics, and the same name as a ``TraceAnnotation`` so that the
    profiler's trace can say what the host was doing in a device gap."""

    def __init__(self, jax):
        self._annotate = jax.profiler.TraceAnnotation
        self.rows: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.time()
        with self._annotate("bench:" + name):
            yield
        self.rows.append((name, t0, time.time()))


class _Feed:
    """Fresh uniform-random token batches from the seed, made on the
    host by one thread ``depth`` steps ahead and put on the device with
    the batch sharding."""

    def __init__(self, jax, seed, vocab, shape, sharding, depth):
        import numpy as np

        self._rng = np.random.default_rng(seed)
        self._np, self._jax = np, jax
        self._vocab, self._shape, self._sharding = vocab, shape, sharding
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self.first_host = self._draw()
        self._thread = threading.Thread(target=self._fill, daemon=True,
                                        name="bench-feed")
        self._thread.start()

    def _draw(self):
        return self._rng.integers(0, self._vocab, self._shape,
                                  dtype=self._np.int32)

    def _fill(self):
        batch = self.first_host
        while not self._stop.is_set():
            item = self._jax.device_put(batch, self._sharding)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.2)
                    break
                except queue.Full:
                    continue
            batch = self._draw()

    def next(self):
        return self._q.get()

    def close(self):
        self._stop.set()
        self._thread.join(timeout=10)


def worker_loop(c: Dict[str, Any]) -> None:
    """Runs in the gang worker.  ``c`` is plain data: the configuration
    file, the traffic file, the run's arguments."""
    t_entry = time.time()
    import jax
    import optax
    from flax.core import meta
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.parallel.mesh import set_global_mesh
    from ray_tpu.parallel.sharding import flax_sharding
    from ray_tpu.train import Checkpoint, session

    conf, traffic = c["config"], c["traffic"]
    entry, assumed = conf["entry"], conf["assumed"]
    chips = c["chips"]

    # every program, however quick to compile, is kept: the next run of
    # this cell in this checkout finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    events = {"hits": 0, "misses": 0, "compiles": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events["compiles"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    for line in range(c["chatter"]):
        print(f"worker chatter line {line}", flush=True)

    # the chips are open already: the gang's setup_jax touched the
    # backend before this loop, so opening them is part of gang_up_s
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    if dev.platform != c["expect_platform"] or len(devices) < chips:
        raise RuntimeError(
            f"the worker was to open {chips} {c['expect_platform']} "
            f"device(s) and opened {device}: no accelerator, no result")
    devices = devices[:chips]

    # -- the program, from the configuration's dotted paths --------------
    t0 = time.time()
    mcfg = dataclasses.replace(
        resolve(entry["config"])(**entry["config_args"]),
        **c["config_overrides"])
    model = resolve(entry["model"])(mcfg)
    sizes = {"n_embd": mcfg.embed_dim, "n_layer": mcfg.num_layers,
             "n_head": mcfg.num_heads, "n_positions": mcfg.max_seq_len,
             "vocab_size": mcfg.vocab_size}
    published = {k: conf[k] for k in sizes}
    if not c["config_overrides"] and sizes != published:
        raise RuntimeError(f"{entry['config']} builds {sizes}, the "
                           f"configuration file says {published}")
    ref_sizes = {"n_layer": sizes["n_layer"], "n_head": sizes["n_head"],
                 "ln_eps": assumed["program_layer_norm_epsilon"]}
    reference = importlib.import_module(conf["reference"])
    tx = optax.adamw(assumed["learning_rate"],
                     weight_decay=assumed["weight_decay"])
    batch, seq = c["batch"], mcfg.max_seq_len
    key = jax.random.PRNGKey(c["seed"] % (2 ** 31))

    # weights: the program's own tree (shapes, dtypes and logical axes
    # from an abstract trace of its init at depth 1, layer 0 standing
    # for every layer), filled on the device in ONE jitted call from the
    # seed.  jit(model.init) would trace, lower and compile every
    # layer's forward to throw it away (22 s a run at 36 layers), and
    # even the abstract trace of 36 layers took 15 s (PERF.md section 6)
    mesh = None
    if chips > 1:
        mesh = build_mesh(MeshConfig(**conf["layout"]["mesh"]),
                          devices=devices)
        set_global_mesh(mesh)  # the flash kernels run per batch shard
    one_layer = resolve(entry["model"])(dataclasses.replace(
        mcfg, **{entry["depth_arg"]: 1}))
    boxed = reference.expand_layers(
        # under the mesh the traced forward needs a batch that splits
        jax.eval_shape(lambda: one_layer.init_params(key, batch=chips)),
        sizes["n_layer"])
    if mesh is not None:
        rules = resolve(entry["rules"])
        shapes, specs = flax_sharding(boxed, rules)
        param_sharding = jax.tree.map(
            lambda _, s: NamedSharding(mesh, s), shapes, specs)
        batch_sharding = NamedSharding(mesh, rules.spec("batch", None))
        replicated = NamedSharding(mesh, P())
    else:
        shapes = meta.unbox(boxed)
        batch_sharding = replicated = jax.sharding.SingleDeviceSharding(dev)
        param_sharding = jax.tree.map(lambda _: replicated, shapes)
    t1 = time.time()
    make = jax.jit(lambda k: reference.init_like(shapes, k),
                   out_shardings=param_sharding).lower(key).compile()
    t2 = time.time()
    params = make(key)
    jax.block_until_ready(params)
    del make
    init_s = time.time() - t0
    init_split = {"shapes_s": t1 - t0, "compile_s": t2 - t1,
                  "run_s": time.time() - t2}
    phases = {"init": dict(events)}

    feed = _Feed(jax, c["seed"], sizes["vocab_size"], (batch, seq),
                 batch_sharding, traffic["prefetch"])
    first_host = feed.first_host

    # -- the plain reference on the first batch, BEFORE the optimizer
    # state is allocated (its stacked copy of the layers is a third of
    # the parameters' size again)
    t0 = time.time()
    ref_sum = jax.jit(lambda p, t: reference.loss_sum(p, t, **ref_sizes))
    total = 0.0
    for i in range(batch):
        row = jax.device_put(first_host[i:i + 1], replicated)
        total += float(ref_sum(params, row))
    ref_loss0 = total / (batch * (seq - 1))
    del ref_sum
    reference_s = time.time() - t0
    phases["reference"] = dict(events)

    opt_state = tx.init(params)  # leaf by leaf: zeros_like keeps sharding

    # -- the one step program ---------------------------------------------
    step = resolve(entry["make_train_step"])(model, tx)
    first = feed.next()
    t0 = time.time()
    lowered = step.lower(params, opt_state, first)
    lower_s = time.time() - t0
    compiled = lowered.compile()
    compile_s = time.time() - t0
    del lowered
    phases["step"] = dict(events)
    mem = compiled.memory_analysis()
    hbm = {"argument": mem.argument_size_in_bytes,
           "output": mem.output_size_in_bytes,
           "temp": mem.temp_size_in_bytes,
           "alias": mem.alias_size_in_bytes}
    program_text = None
    if c["trace"]:
        text = compiled.as_text()
        program_text = {
            "tpu_custom_call": text.count("tpu_custom_call"),
            **{op: text.count(f" {op}(") for op in (
                "all-gather", "all-reduce", "all-to-all", "reduce-scatter",
                "all-gather-start", "all-reduce-start",
                "collective-permute")}}
        del text

    spans = _Spans(jax)
    every = traffic["checkpoint_every"]  # 0: no save, a cycle is a step
    state = {"params": params, "opt": opt_state, "n": 0}
    del params, opt_state
    losses: List[float] = []
    stamps: List[float] = []       # completion of each step
    after_save: List[int] = []     # indices into stamps: first step after
    saves: List[Dict[str, float]] = []

    def cycles(stop_after_s: Optional[float], n_cycles: Optional[int],
               every: int) -> float:
        """Steps until a cycle boundary at or after ``stop_after_s``
        seconds from now, or ``n_cycles`` cycles.  A cycle is one step,
        or ``every`` steps and the save that follows.  Completion is
        stamped ONE STEP BEHIND: dispatch step i+1, then block on step
        i's loss.  Returns the time the last cycle closed, with nothing
        in flight."""
        pending, done, k, t_begin = None, 0, 0, time.time()
        while True:
            with spans("data"):
                batch_now = feed.next()
            with spans("dispatch"):
                state["params"], state["opt"], loss = compiled(
                    state["params"], state["opt"], batch_now)
            state["n"] += 1
            k += 1
            if pending is not None:
                with spans("wait"):
                    losses.append(float(pending))
                stamps.append(time.time())
            pending = loss
            if k % traffic["report_every"] == 0 and losses:
                with spans("report"):
                    session.report({"step": state["n"],
                                    "loss": losses[-1]})
            if every and k % every:
                continue
            if every:
                # the step donates its parameters to the next one, so
                # the save has to read them before the next dispatch
                with spans("wait"):
                    losses.append(float(pending))
                t_done = time.time()
                stamps.append(t_done)
                pending = None
                with spans("ckpt_serialize"):
                    ckpt = Checkpoint.from_pytree(state["params"])
                t_ser = time.time()
                with spans("report"):
                    session.report({"step": state["n"],
                                    "loss": losses[-1],
                                    "save": len(saves) + 1},
                                   checkpoint=ckpt)
                del ckpt
                now = time.time()
                saves.append({"step": state["n"], "t_done": t_done,
                              "serialize_s": t_ser - t_done,
                              "t_report": t_ser, "t_resume": now})
                after_save.append(len(stamps))
            done += 1
            now = time.time()
            if (n_cycles is not None and done >= n_cycles) or (
                    stop_after_s is not None
                    and now - t_begin >= stop_after_s):
                if pending is not None:
                    with spans("wait"):
                        losses.append(float(pending))
                    now = time.time()
                    stamps.append(now)
                return now

    # first step: the loss at the initial parameters, checked against the
    # reference; then the warm-up, drained so that the window starts as
    # every cycle ends, with nothing in flight
    t0 = time.time()
    state["params"], state["opt"], loss0 = compiled(
        state["params"], state["opt"], first)
    loss0 = float(loss0)
    first_step_s = time.time() - t0
    del first
    cycles(None, traffic["warmup_steps"], 0)
    n_warm = (len(losses), len(stamps), len(saves), len(spans.rows))
    compiles_before = events["compiles"]
    # tracing and lowering left millions of live objects behind; a
    # full collection walking them inside the window stalls the loop for
    # seconds.  Collect once now and keep the survivors out of later
    # collections; what still collects in the window is recorded
    gc_pauses: List[tuple] = []
    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.time()
        else:
            gc_pauses.append((info["generation"], gc_t0[0],
                              time.time() - gc_t0[0]))

    gc.collect()
    gc.freeze()
    gc.callbacks.append(on_gc)

    t_start = time.time()
    t_end = cycles(c["seconds"], None, every)
    compiles_in_window = events["compiles"] - compiles_before
    window = {
        "t_start": t_start, "t_end": t_end,
        "steps": len(stamps) - n_warm[1],
        "stamps": stamps[n_warm[1]:],
        "after_save": [i - n_warm[1] for i in after_save
                       if i >= n_warm[1]],
        "losses": losses[n_warm[0]:],
        "gc_pauses": [p for p in gc_pauses if p[2] > 0.005],
        "saves": saves[n_warm[2]:],
        "spans": spans.rows[n_warm[3]:],
    }

    trace = None
    if c["trace"]:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        mark = (len(stamps), len(saves))
        jax.profiler.start_trace(c["trace_dir"], profiler_options=options)
        t_trace = time.time()
        with spans("traced"):
            t_traced_end = cycles(None, traffic["trace_cycles"], every)
        jax.profiler.stop_trace()
        trace = {"dir": c["trace_dir"], "t0": t_trace, "t1": t_traced_end,
                 "steps": len(stamps) - mark[0],
                 "saves": len(saves) - mark[1]}
    feed.close()
    # after everything the runs share: a kernel traced BEFORE the step
    # changes the step's cache key, and the traced run of a cell would
    # compile its step a second time
    grad_err = _gradient_check(jax, c, conf, reference, ref_sizes,
                               chips) if c["trace"] else None

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    session.report({"final": {
        "device": device, "memory_peak_bytes": peak,
        "t_entry": t_entry, "init_s": init_s, "init_split": init_split,
        "reference_s": reference_s, "lower_s": lower_s,
        "compile_s": compile_s, "first_step_s": first_step_s,
        "cache": dict(events), "cache_after": phases,
        "compiles_in_window": compiles_in_window,
        "loss0": loss0, "ref_loss0": ref_loss0, "grad_err": grad_err,
        "loss_rtol": reference.LOSS_RTOL, "grad_rtol": reference.GRAD_RTOL,
        "hbm": hbm, "program_text": program_text, "window": window,
        "trace": trace, "sizes": sizes, "batch": batch, "seq": seq,
        "remat": bool(getattr(mcfg, "remat", "")),
    }})


def _gradient_check(jax, c, conf, reference, ref_sizes, chips):
    """Program against ``jax.grad`` of the reference at the published
    widths with depth cut to 2, on a few sequences.  Traced run only:
    one small extra compile, kept out of the untraced set-up."""
    import numpy as np
    from flax.core import meta

    entry = conf["entry"]
    depth = min(2, ref_sizes["n_layer"])
    mcfg = dataclasses.replace(
        resolve(entry["config"])(**entry["config_args"]),
        **{**c["config_overrides"], entry["depth_arg"]: depth})
    model = resolve(entry["model"])(mcfg)
    loss_fn = resolve(entry["loss_fn"])
    n_seq = max(2, chips)
    tokens = np.random.default_rng(c["seed"] + 1).integers(
        0, mcfg.vocab_size, (n_seq, mcfg.max_seq_len), dtype=np.int32)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(1), batch=n_seq)))
    params = jax.jit(lambda k: reference.init_like(shapes, k))(
        jax.random.PRNGKey(1))
    sizes = {**ref_sizes, "n_layer": depth}

    @jax.jit
    def error(p, t):
        return reference.grad_error(
            jax.grad(lambda q: loss_fn(model, q, t))(p),
            jax.grad(lambda q: reference.loss(q, t, **sizes))(p))

    return float(error(params, tokens))


# ---------------------------------------------------------------------------
# the parent: starts the runtime, owns no device
# ---------------------------------------------------------------------------

class _SaveWatcher(threading.Thread):
    """Stamps the moment each ``checkpoint_00000N`` is whole on the
    driver's disk (``CheckpointManager.register`` writes
    ``.metrics.json`` last); retention deletes old ones, so look often."""

    def __init__(self, storage: str):
        super().__init__(daemon=True, name="bench-save-watcher")
        self.storage = storage
        self.seen: Dict[str, float] = {}
        self._halt = threading.Event()

    def run(self):
        while not self._halt.is_set():
            self.scan()
            self._halt.wait(0.05)

    def scan(self):
        for path in glob.glob(os.path.join(
                self.storage, "checkpoint_*", ".metrics.json")):
            name = os.path.basename(os.path.dirname(path))
            if name not in self.seen:
                try:
                    self.seen[name] = os.path.getmtime(path)
                except OSError:
                    pass

    def close(self):
        self._halt.set()
        self.join(timeout=5)
        self.scan()


def _worker_log_tails(session_dir: str, limit: int = 3000) -> None:
    for path in sorted(glob.glob(os.path.join(session_dir, "logs", "*.err"))):
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - limit))
            tail = f.read().decode(errors="replace").strip()
        if tail:
            print(f"--- {path}\n{tail}", file=sys.stderr)


def run(cell: Dict[str, Any], args, t_process_start: float,
        rehearse: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Driver side.  Returns the run record; raises ``BenchFailure``
    (no result is printed) when the worker saw anything but the chips
    the cell asks for.  ``rehearse`` (tests only): the same control
    flow at a tiny size on the CPU, never a device result."""
    conf, traffic = cell["config"], cell["traffic"]
    chips = cell["workload"]["chips"]

    def out_of_time():
        print(f"[bench] FAILED: not done after {DEADLINE_S:.0f} s",
              file=sys.stderr, flush=True)
        os._exit(4)  # the head and its workers die with this process

    watchdog = threading.Timer(DEADLINE_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()

    import ray_tpu
    from ray_tpu.core import native
    from ray_tpu.train import (CheckpointConfig, JaxTrainer, RunConfig,
                               ScalingConfig)

    session_dir = None
    os.makedirs(SCRATCH, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="run_", dir=SCRATCH)
    watcher = _SaveWatcher(os.path.join(scratch, "storage"))
    try:
        native.build()  # once, before any daemon or worker needs it
        import jax  # imported, never initialised here
        from jax._src import xla_bridge

        info = ray_tpu.init(_system_config={"log_to_driver": False})
        session_dir = info["session_dir"]
        have = ray_tpu.cluster_resources().get("TPU", 0)
        tpus = 0 if rehearse else chips
        if have < tpus:
            raise BenchFailure(
                f"this host has {have:g} TPU chip(s), the cell needs "
                f"{chips}: no accelerator, no result")
        loop_config = {
            "config": conf, "traffic": traffic, "chips": chips,
            "seed": args.seed, "seconds": args.seconds,
            "trace": bool(args.trace),
            "trace_dir": os.path.join(scratch, "trace"),
            "batch": (rehearse or {}).get("batch",
                                          conf["assumed"]["batch"]),
            "config_overrides": (rehearse or {}).get("config_args", {}),
            "chatter": (rehearse or {}).get("chatter", 0),
            "expect_platform": "cpu" if rehearse else "tpu",
        }
        keep = (traffic.get("checkpoint") or {}).get("num_to_keep")
        watcher.start()
        t_fit = time.time()
        result = JaxTrainer(
            worker_loop, train_loop_config=loop_config,
            scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=1,
                                         tpus_per_worker=tpus),
            run_config=RunConfig(
                storage_path=watcher.storage,
                checkpoint_config=CheckpointConfig(num_to_keep=keep)),
        ).fit()
        t_fit_end = time.time()
        watcher.close()
        if result.error is not None:
            raise BenchFailure(f"train loop failed:\n{result.error}")
        final = (result.metrics or {}).get("final")
        if not final:
            raise BenchFailure("the gang finished without a final report")
        want = "cpu" if rehearse else "tpu"
        if final["device"]["platform"] != want:
            raise BenchFailure(f"the worker opened {final['device']}")
        ray_tpu.shutdown()
        if xla_bridge.backends_are_initialized():
            raise BenchFailure("the parent initialised a jax backend: it "
                               "would hold the chip")
        record = _record(final, cell, t_process_start, t_fit, t_fit_end,
                         watcher.seen, on_chip=not rehearse)
    except BaseException as e:  # noqa: BLE001 — reported, then fatal
        import traceback

        traceback.print_exc()
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        if session_dir:
            _worker_log_tails(session_dir)
        if watcher.is_alive():
            watcher.close()
        ray_tpu.shutdown()
        shutil.rmtree(scratch, ignore_errors=True)
        sys.exit(1)
    watchdog.cancel()
    record["scratch"] = scratch
    return record


def _record(final, cell, t_process_start, t_fit, t_fit_end, seen_saves,
            on_chip) -> Dict[str, Any]:
    """The worker's report and the parent's own stamps, as one record:
    end-to-end values, what ``correct`` rests on, and the raw material
    the per-layer readers take their numbers from."""
    w = final["window"]
    chips = cell["workload"]["chips"]
    window_s = w["t_end"] - w["t_start"]
    tokens = w["steps"] * final["batch"] * final["seq"]
    skip = set(w["after_save"])
    edges = [w["t_start"]] + w["stamps"]
    intervals = [b - a for i, (a, b) in enumerate(zip(edges, edges[1:]))
                 if i not in skip]
    stalls = [s["t_resume"] - s["t_done"] for s in w["saves"]]
    # the first dispatch after each save: it returns late while the
    # worker pickles the reply that carries the checkpoint
    redispatch = []
    for save in w["saves"]:
        after = [t1 - t0 for name, t0, t1 in w["spans"]
                 if name == "dispatch" and t0 >= save["t_resume"] - 1e-3]
        redispatch += after[:1]
    end_to_end = {
        "tokens_per_s_per_chip": tokens / window_s / chips,
        "setup_s": w["t_start"] - t_process_start,
    }
    if intervals:
        end_to_end["step_ms_p90"] = 1e3 * float(
            numpy.percentile(intervals, 90))
    if stalls:
        end_to_end["save_stall_ms"] = 1e3 * statistics.median(stalls)

    # a save counts when its directory was whole on the driver's disk
    # when fit() returned; the window's saves are the first ones
    n_reported = len(w["saves"]) + (final["trace"] or {}).get("saves", 0)
    landed = [t for _, t in sorted(seen_saves.items())]
    failed_saves = max(0, n_reported - len(landed))
    to_disk = [t - s["t_report"] for t, s in zip(landed, w["saves"])]

    ref = final["ref_loss0"]
    checks = {
        "first_loss_matches_reference":
            abs(final["loss0"] - ref) <= final["loss_rtol"] * abs(ref),
        "losses_finite": all(math.isfinite(x) for x in w["losses"])
            and math.isfinite(final["loss0"]),
        "no_compile_in_window": final["compiles_in_window"] == 0,
        "steps_done": w["steps"] > 0,
        "saves_landed": failed_saves == 0,
    }
    if final["grad_err"] is not None:
        checks["gradients_match_reference"] = \
            final["grad_err"] <= final["grad_rtol"]
    if final["program_text"] is not None and on_chip:
        checks["kernels_compiled_in"] = \
            final["program_text"]["tpu_custom_call"] > 0
    return {
        "end_to_end": end_to_end,
        "correct": all(checks.values()), "checks": checks,
        "attempted": w["steps"] + len(w["saves"]),
        "failed": failed_saves,
        "device": final["device"],
        "memory_peak_bytes": final["memory_peak_bytes"],
        "final": final, "chips": chips,
        "step_intervals_s": intervals, "save_stalls_s": stalls,
        "redispatch_s": redispatch,
        "save_to_disk_s": to_disk, "window_s": window_s,
        "gang_up_s": final["t_entry"] - t_fit,
        "teardown_s": t_fit_end - w["t_end"],
        "config": cell["config"], "traffic": cell["traffic"],
        "step_module": cell["config"]["entry"]["step_module"],
    }
