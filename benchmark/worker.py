"""The benchmark's worker: the one file the cell's TPUJob names as its
container command. ``examples/llama_worker.py`` with the configuration read
from the cell's files, weights drawn on the device from the seed, a fresh
seeded batch every step, and a clock around ``Trainer.train_step``.

Set-up builds ONE trainer and ONE state and drives them from the seed
through the first STEPS steps, each synced, reading what ``correct``
compares (each loss, the first gradient from Adam's first moment and the
step's own ``grad_norm``, the parameters' change); the same objects then
run the window inside the same ``run_elastic`` call. The window opens on a
sync and closes on a sync, on a step boundary; one step is kept queued
behind the one that runs, so the device never waits for the host and the
host's clock sees each step end.
``run_elastic`` is left through its ``membership`` callable, which raises
once the window has closed: no step of a cell saves, so none is written.

After the window: peak memory is read, the program's state is freed, and
the plain reference (``check.py``) follows the same first steps on the same
chips; then the trace, if one was taken, is reduced.
"""

import os
import sys
import time

_T_START = time.monotonic()  # before the jax import: set-up counts it

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import importlib
import json
import traceback

import jax
import jax.numpy as jnp
import numpy as np

import check
import weights

WARM_STEPS = check.STEPS  # the steps ``correct`` follows are the warm-up


class WindowClosed(Exception):
    """Raised through run_elastic, from its membership call, to end it."""


def _first_moment(opt_state):
    """Adam's first moment, wherever the optimizer chain keeps it."""
    found = []

    def walk(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} Adam states in the optimizer")
    return found[0]


class TimedTrainer:
    """The trainer run_elastic steps, with the benchmark's clock and
    readings around each ``train_step``. Everything else is the trainer's."""

    def __init__(self, trainer, *, seconds, tokens_per_step, beta1,
                 to_flat, delta_norms, fault):
        self._trainer = trainer
        self.seconds = seconds
        self.tokens_per_step = tokens_per_step
        self.beta1 = beta1
        self.to_flat = to_flat
        self.delta_norms = delta_norms
        self.fault = fault
        self.n = 0
        self.readings = {"loss": []}
        self.pending = []       # losses dispatched and not yet waited for
        self.ends = []          # host clock at each window step's end
        self.t_open = self.t_close = None
        self.input_s = 0.0      # the feed's seconds inside the window

    def __getattr__(self, name):
        return getattr(self._trainer, name)

    @property
    def in_window(self):
        return self.t_open is not None and self.t_close is None

    def train_step(self, state, batch):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            new_state, metrics = self._trainer.train_step(state, batch)
        if self.fault == "state_unchanged":
            # the fault a test plants: the step's work is thrown away (the
            # trainer was built without donation, so ``state`` still lives)
            new_state = state
        self.n += 1
        if self.n <= WARM_STEPS:
            self._warm(new_state, metrics)
        else:
            self._timed(metrics)
        return new_state, metrics

    def _warm(self, state, metrics):
        self.readings["loss"].append(float(metrics["loss"]))
        if self.n == 1:
            # the whole gradient's norm before the clip, where the step
            # says it (the trainer does only beside a clip)
            if "grad_norm" in metrics:
                self.readings["gnorm"] = float(metrics["grad_norm"])
            mu = jax.tree.map(
                check.norm, self.to_flat(_first_moment(state.opt_state)))
            self.readings["grad_norm"] = {
                k: float(v) / (1.0 - self.beta1) for k, v in mu.items()}
        if self.n == WARM_STEPS:
            self.readings["delta_norm"] = self.delta_norms(state.params)
            self.t_open = time.monotonic()
            self.ends.append(self.t_open)

    def _timed(self, metrics):
        self.pending.append(metrics["loss"])
        if len(self.pending) > 1:
            with jax.profiler.TraceAnnotation("bench.wait"):
                self.pending.pop(0).block_until_ready()
            self.ends.append(time.monotonic())
        if time.monotonic() - self.t_open >= self.seconds:
            with jax.profiler.TraceAnnotation("bench.wait"):
                self.pending.pop(0).block_until_ready()
            self.t_close = time.monotonic()
            self.ends.append(self.t_close)

    def membership(self):
        if self.t_close is not None:
            raise WindowClosed()
        return 1


def _memory_peak():
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return max((p for p in peaks if p is not None), default=None)


def _reference_placement(chips, shapes):
    """Where the reference's arrays go over several chips: each leaf cut
    along its longest axis that divides, a batch along its rows. Returns
    (the draw's out_shardings, the placing of a batch)."""
    if chips == 1:
        return None, lambda batch: batch
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:chips]), ("chips",))

    def leaf_sharding(shape):
        spec = [None] * len(shape)
        axes = [i for i in range(len(shape)) if shape[i] % chips == 0]
        if axes:
            spec[max(axes, key=lambda i: shape[i])] = "chips"
        return NamedSharding(mesh, P(*spec))

    return ({n: leaf_sharding(s) for n, (s, _) in shapes.items()},
            lambda batch: jax.device_put(
                batch, NamedSharding(mesh, P("chips"))))


def main():
    with open(os.environ["BENCH_RUN_FILE"]) as f:
        run = json.load(f)
    with open(run["config_file"]) as f:
        conf = json.load(f)
    with open(run["traffic_file"]) as f:
        tr = json.load(f)
    generator = importlib.import_module(f"generators.{tr['generator']}")
    chips, seed = run["chips"], run["seed"]

    from mpi_operator_tpu.ops import Trainer, TrainerConfig
    from mpi_operator_tpu.ops.data import make_global_batch
    from mpi_operator_tpu.ops.elastic import ElasticConfig, run_elastic
    from mpi_operator_tpu.runtime import (MeshPlan, bootstrap, compile_cache,
                                          mesh_from_context, stepstats)

    ctx = bootstrap.initialize()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if len(devices) != chips:
        raise SystemExit(f"the cell asks for {chips} chips; jax sees "
                         f"{len(devices)} x {device['kind']}")
    mesh = mesh_from_context(
        ctx, MeshPlan.parse(conf["mesh"], "") if conf["mesh"] else None)

    adapter = importlib.import_module(f"adapters.{conf['adapter']}")
    reference = importlib.import_module(f"reference.{conf['reference']}")
    shapes = reference.param_shapes(conf)
    cfg = adapter.config(conf, control=run["control"])
    opt = conf["assumed"]["optimizer"]
    step_loss = adapter.loss_fn(cfg, mesh)
    fault = run["fault"]
    if fault in ("half_batch", "no_exchange"):
        # faults a test plants in the program's place: half the batch left
        # out of the mean, or every chip left with the first chip's rows
        # (what a step is worth whose gradients are never exchanged). Rows
        # kept are repeated, so that shapes stay; a batch of one row loses
        # the second half of its positions instead.
        full_loss, share = step_loss, 2 if fault == "half_batch" else chips

        def cut(a):
            keep = a.shape[0] // share
            if keep:
                return jnp.tile(a[:keep], (a.shape[0] // keep,)
                                + (1,) * (a.ndim - 1))
            return a[:, :a.shape[1] // 2]

        step_loss = lambda p, b: full_loss(p, jax.tree.map(cut, b))
    trainer = Trainer(
        step_loss, adapter.logical_axes(cfg), mesh,
        TrainerConfig(
            learning_rate=opt["learning_rate"], optimizer="adamw",
            weight_decay=opt["weight_decay"], beta1=opt["beta1"],
            beta2=opt["beta2"], grad_clip_norm=opt["grad_clip_norm"]),
        donate=fault != "state_unchanged")
    key = weights.seed_key(seed)
    p_sharding = trainer.params_sharding()
    flat_sharding = adapter.to_flat(p_sharding)

    def init_state():
        draw = jax.jit(lambda k: adapter.to_tree(weights.draw(shapes, k)),
                       out_shardings=p_sharding)
        return trainer.init_state(draw(key))

    def delta_norms(params):
        """Each leaf's norm of (parameters now - parameters drawn), the
        draw made again leaf by leaf so that no second model is held."""
        flat = adapter.to_flat(params)
        out = {}
        for name in shapes:
            fn = jax.jit(
                lambda p, k, name=name: check.norm(
                    p - weights.draw_leaf(shapes, name, k)),
                in_shardings=(flat_sharding[name], None))
            out[name] = float(fn(flat[name], key))
        return out

    timed = TimedTrainer(
        trainer, seconds=run["seconds"],
        tokens_per_step=generator.tokens_per_step(tr, chips),
        beta1=opt["beta1"], to_flat=adapter.to_flat,
        delta_norms=delta_norms, fault=fault)

    marks = {"worker_start": _T_START}

    def batches():
        step = 0
        while True:
            step += 1
            t0 = time.monotonic()
            if step == 1:
                marks["first_batch"] = t0
            with jax.profiler.TraceAnnotation("bench.input"):
                b = make_global_batch(
                    mesh, generator.batch(conf, tr, seed, step, chips))
            if timed.in_window:
                timed.input_s += time.monotonic() - t0
            yield b

    ckpt_dir = os.path.join(run["run_dir"], "ckpt")
    try:
        run_elastic(
            timed, batches(), total_steps=10 ** 9,
            config=ElasticConfig(checkpoint_dir=ckpt_dir,
                                 save_interval_steps=10 ** 9,
                                 membership_check_every=1),
            init_state=init_state, membership=timed.membership)
        raise SystemExit("run_elastic returned before the window closed")
    except WindowClosed as e:
        traceback.clear_frames(e.__traceback__)

    steps = timed.n - WARM_STEPS
    window_s = timed.t_close - timed.t_open
    step_ms = [1e3 * (b - a) for a, b in zip(timed.ends, timed.ends[1:])]
    report = {
        "device": dict(device, memory_peak_bytes=_memory_peak()),
        "steps": steps, "window_s": window_s,
        "tokens": steps * timed.tokens_per_step,
        "step_ms": step_ms, "input_s": timed.input_s,
        "marks": dict(marks, window_open=timed.t_open,
                      window_close=timed.t_close),
        "compile_cache": compile_cache.cache_stats(),
        "stepstats": stepstats.read_stats(
            os.environ.get(stepstats.ENV_STATS_FILE, "")),
        "program": timed.readings,
    }
    program = timed.readings

    # free the program's state before the reference takes the chip: the
    # last references to it died with run_elastic's frames
    del timed, trainer
    t_ref = time.monotonic()
    leaf_shardings, place_tokens = _reference_placement(chips, shapes)
    ref_params = jax.jit(lambda k: weights.draw(shapes, k),
                         out_shardings=leaf_shardings)(key)
    ref_batches = [generator.batch(conf, tr, seed, s, chips)
                   for s in range(1, check.STEPS + 1)]
    ref = check.reference_steps(
        lambda p, b: reference.loss(conf, p, b, chips=chips),
        ref_params, lambda name: weights.draw_leaf(shapes, name, key),
        ref_batches, opt, place=place_tokens, shardings=leaf_shardings)
    numbers = check.compare(program, ref)
    report["reference"] = ref
    report["reference_s"] = time.monotonic() - t_ref
    report["numbers"] = {k: list(v) for k, v in numbers.items()}

    if run["trace_dir"]:
        import trace_reduce

        report["trace"] = trace_reduce.reduce_dir(run["trace_dir"])
    with open(run["report_file"], "w") as f:
        json.dump(report, f)


if __name__ == "__main__":
    main()
