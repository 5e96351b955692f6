"""One run of one benchmark cell: set-up, the measured window, the check.

A cell is a workload of ``BENCHMARK.json``: a model configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<traffic>.json``),
with the limits of its comparison in ``limits/<workload>.json`` and one reader
per per-layer metric in ``metrics/<name>.py``. A configuration names its
model family's plain reference, ``refs/<reference>.py``, which initialises the
weights, computes the loss and counts the family's operations per token
(``forward_flops``, read by ``flops.py``). Everything is found by the names
``BENCHMARK.json`` and the configuration give, so a cell, a configuration, a
model family or a metric is added with new files and new entries only. A
reader gets a ``ctx`` from :func:`reader_context`, the cell's configuration and
traffic among it.

The run:

1. refuses to run without the TPUs the cell asks for, or on a device kind
   that ``peaks.json`` does not list;
2. builds the trainer as ``repro.launch.train.run`` builds it for the cell's
   engine, with weights made on the device from the seed by the reference
   model's own initialiser, and token batches drawn from the seed;
3. drives the trainer's own ``step`` on the window's own feed through the
   first three steps (they compile every program the window uses) and reads
   back the losses, the first gradient and the parameters' change;
4. measures: ``trainer.step`` back to back for ``--seconds``, waiting only on
   the loss of the step two back so that at most three steps are in flight,
   then blocks on the state (``--trace 1``: the same under the profiler);
5. frees the program's state and runs the plain reference over the same
   three steps, in float32 at ``highest`` matmul precision, and compares.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

CHIP = Path(__file__).resolve().parent
CHECKOUT = CHIP.parents[1]
CHECK_STEPS = 3           # steps the reference follows
LAG = 2                   # the window waits on the loss of the step LAG back
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"


class Refused(SystemExit):
    """The run cannot measure this cell here; exits non-zero, no result."""

    def __init__(self, why: str):
        super().__init__(f"run_cell: {why}")


# --------------------------------------------------------------------- cell
def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = CHECKOUT) -> SimpleNamespace:
    """Resolves ``workload`` through ``root/BENCHMARK.json`` to its files."""
    bench_file = root / "BENCHMARK.json"
    if not bench_file.is_file():
        raise Refused(f"no BENCHMARK.json in {root}")
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    data = root / bench["paths"][0]
    metrics = []
    for m in bench["per_layer"]:
        if workload in m.get("workloads", [workload]):
            metrics.append((m, _module(data / "metrics" / f"{m['name']}.py",
                                       f"metric_{m['name'].replace('.', '_')}")))
    return SimpleNamespace(
        name=workload, chips=cell["chips"],
        config=json.loads((root / config["file"]).read_text()),
        traffic=json.loads((data / "traffic" / f"{cell['traffic']}.json").read_text()),
        limits=json.loads((data / "limits" / f"{workload}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"]
                    if workload in m.get("workloads", [workload])],
        metrics=metrics,
        peaks=json.loads((CHIP / "peaks.json").read_text()))


def require_chips(cell):
    """The first ``cell.chips`` TPUs and their peaks; refuses anything else."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < cell.chips:
        raise Refused(f"the cell needs {cell.chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in cell.peaks:
        raise Refused(f"device kind {kind!r} is not in peaks.json")
    return devices[:cell.chips], cell.peaks[kind]


def reference_model(cfg: dict):
    if str(CHIP) not in sys.path:
        sys.path.insert(0, str(CHIP))
    return importlib.import_module(f"refs.{cfg['reference']}")


def model_config(cfg: dict):
    """The program's ModelConfig from the configuration file's fields."""
    import dataclasses
    import typing

    from repro.common import config as C
    hints = typing.get_type_hints(C.ModelConfig, vars(C))
    kw = {}
    for f in dataclasses.fields(C.ModelConfig):
        if f.name not in cfg:
            continue
        v = cfg[f.name]
        if isinstance(v, dict):
            sub = typing.get_args(hints[f.name])[0]      # Optional[X] -> X
            v = sub(**v)
        elif isinstance(v, list):
            v = tuple(v)
        kw[f.name] = v
    return C.ModelConfig(**kw)


def seeds(seed: int) -> SimpleNamespace:
    """Independent sub-seeds of ``--seed`` (any size) for the weights, the
    token rows and the trainer's own key."""
    words = np.random.SeedSequence(seed).generate_state(3)
    return SimpleNamespace(weights=int(words[0]), data=int(words[1]),
                           trainer=int(words[2] >> 1))


# ----------------------------------------------------------------- trainer
def build_trainer(cell, mcfg, make_weights, devices, seed: int):
    """(trainer, state, as_batch): the trainer as ``repro.launch.train.run``
    builds it for the sim engine, its state holding ``make_weights()`` on
    every replica, and the map from a ``(tokens, labels)`` batch to its step
    input."""
    import jax

    from repro.api import GossipTrainer
    from repro.common.config import OptimizerConfig, ProtocolConfig
    from repro.models import transformer as tr

    t = cell.traffic
    if t["engine"] != "sim":
        raise Refused(f"no harness for engine {t['engine']!r}")
    proto = ProtocolConfig(method=t["method"], moving_rate=t["alpha"],
                           comm_probability=t["p"], comm_period=0,
                           codec=t["codec"])
    opt = OptimizerConfig(name="nag", learning_rate=t["lr"], momentum=t["momentum"])

    def loss_fn(params, x, y):
        return tr.lm_loss(params, mcfg, x, y)[0]

    trainer = GossipTrainer(engine="sim", protocol=proto, optimizer=opt,
                            loss_fn=loss_fn, num_workers=t["workers"], seed=seed)
    state = trainer.init_state(seed, params=make_weights())

    def as_batch(b):
        return (jax.device_put(b[0], devices[0]), jax.device_put(b[1], devices[0]))
    return trainer, state, as_batch


# --------------------------------------------------------------------- run
def enable_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every program however fast it compiles, so that a warm run compiles
    nothing. Returns the directory."""
    import jax

    from repro.common.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Clock:
    """Backend compiles and cache traffic, as JAX reports them."""

    def __init__(self):
        import jax
        self.compiles, self.hits, self.misses = [], 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == COMPILE:
            self.compiles.append((time.perf_counter(), duration))

    def _event(self, event, **kw):
        if event == CACHE_HITS:
            self.hits += 1
        elif event == CACHE_MISSES:
            self.misses += 1

    def within(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.compiles if t0 <= t <= t1)


def prepare(cell, seed: int, devices) -> SimpleNamespace:
    """Set-up up to the window: weights and rows from the seed, the trainer,
    and its first ``CHECK_STEPS`` steps with their readings (``prog``)."""
    import jax

    import compare
    import feed
    from repro.models import transformer as tr

    cfg, t = cell.config, cell.traffic
    model = reference_model(cfg)
    mcfg = model_config(cfg)
    sd = seeds(seed)
    # weights: one jitted call from the seed, on the device
    make_params = jax.jit(lambda k: model.init(cfg, k))
    key = jax.random.PRNGKey(sd.weights)
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)), tr.abstract_lm(mcfg)[0])
    got = jax.tree.map(lambda s: (s.shape, str(s.dtype)), jax.eval_shape(make_params, key))
    if want != got:
        raise Refused("the reference initialiser's layout differs from the "
                      "program's parameter tree")
    trainer, state, as_batch = build_trainer(cell, mcfg, lambda: make_params(key),
                                             devices, sd.trainer)
    pool_np = feed.batches(sd.data, t, cfg["vocab_size"])
    pool = [as_batch(b) for b in pool_np]
    spec = state.spec

    @jax.jit
    def grad_norms(mu):
        return compare.leaf_norms(spec.unflatten(mu), lead=1) / t["lr"]

    @jax.jit
    def change_norms(theta, p0):
        return compare.leaf_norms(jax.tree.map(lambda a, b: a - b[None],
                                               spec.unflatten(theta), p0), lead=1)

    # the first steps: the window's own call and feed, every row its own
    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        state, m = trainer.step(state, pool[i])
        prog["losses"].append(float(m["loss"]))
        if i == 0:
            prog["grad"] = np.asarray(grad_norms(state.opt.mu))
    prog["change"] = np.asarray(change_norms(state.theta, make_params(key)))
    jax.block_until_ready(state.theta)
    gc.collect()
    return SimpleNamespace(trainer=trainer, state=state, pool=pool,
                           pool_np=pool_np, make_params=make_params, key=key,
                           model=model, seeds=sd, prog=prog)


def reference(cell, run, devices, dtype=None, fault: str = "",
              precision: str = "highest") -> dict:
    """Readings of the plain reference over the first ``CHECK_STEPS`` steps
    of ``run``'s rows and weights: float32 at ``highest`` matmul precision,
    or ``dtype`` (the control), a ``fault`` planted or another ``precision``
    (calibration). ``names`` labels the leaves of the norm readings."""
    import jax
    import jax.numpy as jnp

    import compare
    algorithm = importlib.import_module(f"refs.{cell.traffic['method']}")

    with jax.default_matmul_precision(precision):
        batches = [tuple(jnp.asarray(x) for x in run.pool_np[s])
                   for s in range(CHECK_STEPS)]
        r_losses, r_grads, r_thetas = algorithm.train(
            run.model, cell.config, cell.traffic, run.make_params(run.key),
            batches, run.seeds.trainer, steps=CHECK_STEPS, devices=devices,
            dtype=dtype or jnp.float32, fault=fault)
        p0 = run.make_params(run.key)
        return {"losses": r_losses, "names": compare.leaf_names(p0),
                "grad": np.stack([np.asarray(compare.leaf_norms(g))
                                  for g in r_grads], 1),
                "change": np.stack([np.asarray(compare.leaf_norms(
                    jax.tree.map(lambda a, b: a - b, th, p0)))
                    for th in r_thetas], 1)}


def reader_context(cell, trace_path: str, steps: int, chips: int, peak: dict):
    """(trace, ctx): the trace of a run's window at ``trace_path`` and what
    the per-layer readers see of the run: its first ``chips`` devices' ops
    clipped to the ``window`` span and their busy seconds, the window's length,
    the steps and tokens in it, the chips' peaks, the required operations per
    token, the cell's configuration and traffic, and the trace's path."""
    import devtrace
    import flops
    tr = devtrace.load(trace_path)
    lo, hi = devtrace.window(tr)
    ops = {d: devtrace.clip(o, lo, hi) for d, o in tr.ops.items() if d < chips}
    t = cell.traffic
    return tr, SimpleNamespace(
        ops=ops, window_s=(hi - lo) / 1e9,
        busy_s=[devtrace.busy_ns(o) / 1e9 for o in ops.values()], steps=steps,
        tokens=steps * t["workers"] * t["per_worker_batch"] * t["seq"],
        chips=chips, peak=peak,
        flops_per_token=flops.train_flops_per_token(cell.config, t["seq"]),
        config=cell.config, traffic=t, trace_path=trace_path)


def run(args, root: Path = CHECKOUT, t_start: float = None) -> dict:
    """One run; returns the result object (the last line of stdout)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(args.workload, root)
    import jax

    devices, peak = require_chips(cell)
    import compare
    import devtrace

    cache_dir = enable_cache()
    clock = Clock()
    t = cell.traffic
    W, B, S = t["workers"], t["per_worker_batch"], t["seq"]
    prep = prepare(cell, args.seed, devices)
    trainer, state, pool = prep.trainer, prep.state, prep.pool
    prep.trainer = prep.state = prep.pool = None

    tokens_per_step = W * B * S
    trace_dir = tempfile.mkdtemp(prefix="chip_trace_") if args.trace else None
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, t["trace_seconds"])
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    losses = []
    ann = jax.profiler.TraceAnnotation
    with ann("window"):
        t0 = time.perf_counter()
        i = 0
        while True:
            with ann("batch"):
                batch = pool[(CHECK_STEPS + i) % len(pool)]
            with ann("dispatch"):
                state, m = trainer.step(state, batch)
            losses.append(m["loss"])
            i += 1
            if i > LAG:
                with ann("block"):
                    jax.block_until_ready(losses[i - 1 - LAG])
            if time.perf_counter() - t0 >= seconds:
                break
        with ann("block"):
            jax.block_until_ready(state.theta)
        t1 = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    steps = len(losses)
    window_losses = np.asarray([float(x) for x in losses])
    compiles_in_window = clock.within(t0, t1)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    failed = int(np.sum(~np.isfinite(window_losses)))

    # free the program's state before the reference runs
    del state, m, losses, pool, trainer, batch
    gc.collect()
    ref = reference(cell, prep, devices)
    values = compare.gaps(prep.prog, ref)
    correct, checks = compare.verdict(values, cell.limits)
    correct = correct and failed == 0

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    print(json.dumps({"compiles": {
        "cache_dir": cache_dir, "backend_compiles": len(clock.compiles),
        "compile_s": sum(d for _, d in clock.compiles), "cache_hits": clock.hits,
        "cache_misses": clock.misses, "compiles_in_window": compiles_in_window}}),
        flush=True)
    print(json.dumps({"worst_leaves": compare.worst_leaves(prep.prog, ref, ref["names"])}),
          flush=True)
    print(json.dumps({"window": {"steps": steps, "seconds": window_s,
                                 "losses_first_last": [window_losses[0], window_losses[-1]],
                                 "program": prep.prog["losses"], "reference": ref["losses"]}}),
          flush=True)
    result = {"correct": correct, "attempted": steps, "failed": failed}
    if args.trace:
        tr_, ctx = reader_context(cell, devtrace.xplane_file(trace_dir), steps,
                                  len(devices), peak)
        ops, busy = ctx.ops, ctx.busy_s
        lo, hi = devtrace.window(tr_)
        metrics = {}
        for entry, mod in cell.metrics:
            v = mod.read(ctx)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = ctx.window_s
        first = min(ops) if ops else None
        result["device"] = device
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(ops, 10),
            "idle_gaps": (devtrace.longest_gaps(tr_, ops[first], lo, hi, 10)
                          if first is not None else [])}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values_e2e = {
            "train_tokens_per_s": ("tokens/s", steps * tokens_per_step / window_s),
            "peak_hbm_gb": ("GB", peak_bytes / 1e9),
            "setup_s": ("s", setup_s)}
        result["metrics"] = {m["name"]: {"value": values_e2e[m["name"]][1],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = checks
    return result
