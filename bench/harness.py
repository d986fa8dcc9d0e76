"""One benchmark run: build a cell, train through the program, measure, check.

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``
and the model file ``bench/models/<model_type>.py`` it names) and a traffic
mix (``bench/traffic/<name>.json``); each metric is read by
``bench/metrics/<name>.py``.  The harness finds all of them by name, so a
cell or a metric is added by adding files and entries.

The run composes the program as its training launcher does, from public
APIs: ``DataPlaneSpec(...).build_runtime(RealClock())`` gives this rank's
``DeliLoader`` and prefetch service over a bucket of the benchmark's own
token objects; ``Trainer`` trains on it.  The window is one
``Trainer.train`` call.  The trainer is handed a ``TimedFeed`` over the
loader: it stamps every batch request, and ends the epoch once the window's
seconds are up.  The first requests of the call are the warm-up.  In the
window the harness only stamps each request and compares what passes with
the benchmark's objects, keeping counts and one time stamp a step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import sys
import time
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.bucket import TableIBucket

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"

# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------
def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"bench_file_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything its files say."""

    name: str
    chips: int
    conf: Dict  # the configuration file
    traffic: Dict  # the traffic file
    model: ModuleType  # bench/models/<model_type>.py
    end_to_end: List[Dict]  # metric entries this cell reports untraced
    per_layer: List[Dict]  # ... and traced

    @property
    def batch(self) -> int:
        return self.conf["train"]["batch"]

    @property
    def seq_len(self) -> int:
        return self.conf["train"]["seq_len"]


def load_cell(name: str) -> Cell:
    bm = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    config = {c["name"]: c for c in bm["configs"]}[entry["config"]]
    conf = load_json(ROOT / config["file"])
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    model = load_module(BENCH / "models" / f"{conf['model_type']}.py")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(
        name, entry["chips"], conf, traffic, model, mine(bm["end_to_end"]), mine(bm["per_layer"])
    )


# ---------------------------------------------------------------------------
# The chip
# ---------------------------------------------------------------------------
def require_devices(chips: int):
    """The devices of a TPU host with at least ``chips`` chips, or exit."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU here (JAX platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def enable_caches() -> None:
    """JAX's persistent compilation cache in the checkout, for every program
    the run compiles (small ones too), so that a second run compiles
    nothing.  The path is fixed (it is part of the cache's key) and is the
    checkout's even where ``JAX_COMPILATION_CACHE_DIR`` names another, so
    that two checkouts compared on one machine share no compiled program."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# The loader as the trainer sees it
# ---------------------------------------------------------------------------
class DataCheck:
    """Counts the samples served out of the sampler's order and the rows the
    trainer decoded with other tokens than the benchmark's objects, as they
    pass, keeping only counts."""

    def __init__(self, tokens: np.ndarray, order: np.ndarray):
        self.tokens, self.order = tokens, order
        self.served = self.decoded = self.bad = 0

    def serve(self, indices) -> None:
        for index in indices:
            self.bad += index != self.order[self.served]
            self.served += 1

    def decode(self, row: np.ndarray) -> None:
        self.bad += not np.array_equal(row, self.tokens[self.order[self.decoded]])
        self.decoded += 1

    @property
    def mismatch(self) -> int:
        return int(self.bad) + abs(self.served - self.decoded)


class TimedFeed:
    """The rank's ``DeliLoader`` with the benchmark's clock on it.

    Every attribute is the loader's.  Iterating stamps each batch request,
    calls ``on_request(k)`` first during the warm-up (k counts requests from
    1), hands each batch's indices to ``data``, and ends the epoch at the
    first request once ``seconds`` have passed since request ``warmup + 1``,
    which opens the window.
    """

    def __init__(self, loader, warmup: int, seconds: float, annotate, data: DataCheck):
        self._loader = loader
        self.warmup = warmup
        self.seconds = seconds
        self.annotate = annotate
        self.data = data
        self.on_request: Callable[[int], None] = lambda k: None
        self.requests: List[float] = []
        self.window_start: Optional[float] = None
        self.window_end: Optional[float] = None

    def __getattr__(self, name):
        return getattr(self._loader, name)

    def __iter__(self):
        batches = iter(self._loader)
        while True:
            k = len(self.requests) + 1
            if k <= self.warmup + 1:
                self.on_request(k)
            now = time.perf_counter()
            self.requests.append(now)
            if k == self.warmup + 1:
                self.window_start = now
            elif self.window_start is not None and now - self.window_start >= self.seconds:
                self.window_end = now
                return
            with self.annotate("bench.next_batch"):
                batch = next(batches, None)
            if batch is None:
                raise RuntimeError("the partition ran out before the window closed")
            self.data.serve(batch.indices)
            yield batch


class Probes:
    """What the program's state says after its first steps, read on the
    device while the warm-up runs: at request 2 the first gradient from the
    first moments, at request 4 the parameters' change over steps 1-3."""

    def __init__(self, trainer, beta1: float, make_params: Callable):
        import jax

        from bench.reference import change_norms

        self.trainer = trainer
        self.make_params = make_params
        self.grad = None  # the first gradient, on the host
        self.delta = None
        self.seconds = 0.0  # spent here: the check's, not the set-up's
        self._grad = jax.jit(lambda m: jax.tree.map(lambda x: x / (1 - beta1), m))
        self._change = change_norms
        self.hooks: List[Callable[[int], None]] = []

    def __call__(self, k: int) -> None:
        import jax

        t0 = time.perf_counter()
        if k == 2:
            self.grad = jax.device_get(self._grad(self.trainer.opt_state["m"]))
        elif k == 4:
            self.delta = [float(x) for x in self._change(self.trainer.params, self.make_params())]
        self.seconds += time.perf_counter() - t0
        for hook in self.hooks:
            hook(k)


def _annotations(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """Everything a metric reader or the check may read after a run."""

    cell: Cell
    seed: int
    setup_s: float
    window_s: float
    step_s: List[float]  # wall time between consecutive batch requests, window steps
    steps: List  # the trainer's StepMetrics of the window steps
    warmup_losses: List[float]
    data_mismatch: int  # DataCheck.mismatch over every batch the trainer took
    tokens: np.ndarray  # the benchmark's objects, (n_objects, seq_len + 1)
    order: np.ndarray  # the indices this rank reads this epoch, in order
    program: Optional[object]  # reference.Readings of the program's first steps
    make_params: Callable
    memory_peak_bytes: int
    phases: Dict[str, float]  # seconds from process start at which each set-up phase ended
    device: Dict
    trace: Optional[object] = None  # trace.Summary of the window, when traced


def build_spec(cell: Cell, objects: Dict[int, bytes], sampler_seed: int):
    """The cell's data plane as the program declares one, over the
    benchmark's own Table-I bucket."""
    from repro.core.policy import PrefetchConfig
    from repro.core.workloads import WorkloadSpec
    from repro.pipeline.spec import DataPlaneSpec

    t = cell.traffic
    prefetch = None
    if t["fetch_size"] is not None:
        prefetch = PrefetchConfig(
            fetch_size=t["fetch_size"],
            prefetch_threshold=t["prefetch_threshold"],
            cache_items=t["cache_items"],
        )
    return DataPlaneSpec(
        workload=WorkloadSpec(
            name=t["name"],
            n_samples=t["n_objects"],
            sample_bytes=(cell.seq_len + 1) * 4,
            batch_size=cell.batch,
            compute_per_epoch_s=0.0,
            n_nodes=t["world"],
        ),
        cache_items=t["cache_items"],
        prefetch=prefetch,
        n_connections=t["n_connections"],
        bucket=TableIBucket(**t["bucket"]),
        payload_factory=lambda spec: objects,
        seed=sampler_seed,
    )


def _install(made, program) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from the program's."""
    import jax

    if jax.tree.structure(made) != jax.tree.structure(program):
        raise RuntimeError(
            f"the benchmark's weights do not fit the program's tree:\n"
            f"{jax.tree.structure(made)}\n{jax.tree.structure(program)}"
        )
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(program)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise RuntimeError(
                f"leaf {a.shape} {a.dtype} where the program has {b.shape} {b.dtype}"
            )


def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, t_start: float, devices
) -> Run:
    """Set up, warm up, measure for ``seconds``, and read the program's state."""
    import jax

    from repro.core import RealClock
    from repro.data import decode_tokens
    from repro.training.loop import Trainer, TrainerConfig
    from repro.training.optimizer import OptSettings

    from bench import payloads, reference

    phases = {"chip": time.perf_counter()}
    enable_caches()
    t = cell.traffic
    tr = cell.conf["train"]
    seeds = payloads.Seeds.from_seed(seed)
    tokens = payloads.make_tokens(
        t["n_objects"], cell.seq_len, cell.model.token_vocab(cell.conf), seeds.data
    )
    phases["objects"] = time.perf_counter()
    cluster = build_spec(cell, payloads.as_objects(tokens), seeds.sampler).build_runtime(
        clock=RealClock()
    )
    phases["data plane"] = time.perf_counter()
    loader, service = cluster.loaders[t["rank"]], cluster.services[t["rank"]]
    if cluster.buckets[t["rank"]].model != TableIBucket(**t["bucket"]):
        raise RuntimeError("the data plane does not read through the cell's bucket")

    annotate = _annotations(trace)
    order = payloads.epoch_order(t["n_objects"], seeds.sampler, 0, t["rank"], t["world"])
    data = DataCheck(tokens, order)

    def decode(payload: bytes) -> np.ndarray:
        with annotate("bench.decode"):
            row = decode_tokens(payload)
        data.decode(row)
        return row

    key = jax.random.PRNGKey(seeds.params)
    init = jax.jit(functools.partial(cell.model.init_params, conf=cell.conf))
    make_params = functools.partial(init, key)  # the key is an argument: one program for all seeds
    feed = TimedFeed(loader, t["warmup_steps"], seconds, annotate, data)
    trainer = Trainer(
        cell.model.arch_config(cell.conf),
        feed,
        TrainerConfig(seq_len=cell.seq_len, batch_size=cell.batch, log_every=10 ** 9),
        decode_fn=decode,
        settings=OptSettings(
            lr=tr["lr"],
            beta1=tr["beta1"],
            beta2=tr["beta2"],
            eps=tr["eps"],
            weight_decay=tr["weight_decay"],
            grad_clip=tr["grad_clip"],
            moment_dtype=tr["moment_dtype"],
        ),
    )
    phases["trainer"] = time.perf_counter()
    made = make_params()
    _install(made, trainer.params)
    trainer.params = made
    del made
    probes = Probes(trainer, tr["beta1"], make_params)
    feed.on_request = probes
    phases["weights"] = time.perf_counter()

    def mark_step_1(k: int) -> None:
        if k == 2:
            phases["step 1"] = time.perf_counter()

    probes.hooks.append(mark_step_1)
    tracer = None
    if trace:
        from bench.trace import Tracer

        tracer = Tracer()

        def open_window(k: int) -> None:
            if k == t["warmup_steps"] + 1:
                tracer.start()

        probes.hooks.append(open_window)

    try:
        with service if service is not None else contextlib.nullcontext():
            trainer.train(num_steps=10 ** 9, epochs=1)
    finally:
        if tracer is not None:
            tracer.stop()
    memory = devices[0].memory_stats() or {}

    w = t["warmup_steps"]
    req = feed.requests
    metrics = trainer.metrics
    program = reference.Readings(
        losses=[m.loss for m in metrics[:3]],
        grad_norms=[float(x) for x in reference.norms(probes.grad)],
        delta_norms=probes.delta,
        first_grad=probes.grad,
    )
    run = Run(
        cell=cell,
        seed=seed,
        setup_s=feed.window_start - t_start - probes.seconds,
        window_s=feed.window_end - feed.window_start,
        step_s=[b - a for a, b in zip(req[w:-1], req[w + 1 :])],
        steps=metrics[w:],
        warmup_losses=[m.loss for m in metrics[:w]],
        data_mismatch=data.mismatch,
        tokens=tokens,
        order=order,
        program=program,
        make_params=make_params,
        memory_peak_bytes=int(memory.get("peak_bytes_in_use", 0)),
        phases={k: v - t_start for k, v in phases.items()},
        device={
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    )
    # Free the program's state before the reference runs on the same chip.
    trainer.params = trainer.opt_state = None
    probes.trainer = None
    del trainer, probes, feed, cluster, loader, service
    gc.collect()
    if tracer is not None:
        run.trace = tracer.summary()
    return run


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------
def first_batches(run: Run, n: int = 3) -> List[Dict[str, np.ndarray]]:
    """The tokens of the first ``n`` steps as the sampler ordered them."""
    B, S = run.cell.batch, run.cell.seq_len
    out = []
    for k in range(n):
        rows = run.tokens[run.order[k * B : (k + 1) * B]][:, : S + 1]
        out.append({"tokens": rows[:, :-1], "labels": rows[:, 1:]})
    return out


def reference_readings(run: Run, precision: str):
    from bench import reference

    cell = run.cell
    return reference.train_reference(
        lambda params, tokens, labels, mm: cell.model.loss(params, tokens, labels, cell.conf, mm),
        run.make_params,
        first_batches(run),
        reference.Adam.from_train(cell.conf["train"]),
        precision,
    )


def checks(run: Run, ref=None) -> Dict[str, Dict[str, float]]:
    """Each number compared, beside its limit from the configuration file."""
    from bench import reference

    if ref is None:
        ref = reference_readings(run, "fp32")
    numbers = reference.compare(run.program, ref)
    numbers["data_mismatch"] = run.data_mismatch
    limits = run.cell.conf["limits"]
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}


def passed(checked: Dict[str, Dict[str, float]]) -> bool:
    return all(
        c["limit"] is not None and c["value"] <= c["limit"] for c in checked.values()
    )


# ---------------------------------------------------------------------------
# The result line
# ---------------------------------------------------------------------------
def read_metrics(run: Run, entries: List[Dict]) -> Dict[str, Dict]:
    out = {}
    for entry in entries:
        reader = load_module(BENCH / "metrics" / f"{entry['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def result(run: Run, trace: bool, checked: Dict) -> Dict:
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    out = {
        "correct": passed(checked),
        "attempted": len(run.steps),
        "failed": sum(1 for m in run.steps if not np.isfinite(m.loss)),
        "metrics": read_metrics(run, run.cell.per_layer if trace else run.cell.end_to_end),
        "device": device,
    }
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = checked
    return out


def describe(run: Run) -> str:
    """Lines for standard error: the window and the steps, for a reader."""
    p90 = float(np.percentile(run.step_s, 90)) if run.step_s else float("nan")
    n = max(len(run.steps), 1)
    wait = sum(m.data_wait_s for m in run.steps) / n
    compute = sum(m.compute_s for m in run.steps) / n
    return (
        f"bench: {run.cell.name} seed {run.seed}: {len(run.steps)} window steps in "
        f"{run.window_s} s, step p90 {p90} s, per step: data-wait {wait} s, "
        f"device step seen from the host {compute} s, rest {run.window_s / n - wait - compute} s; "
        f"setup {run.setup_s} s, "
        f"warm-up losses {run.warmup_losses}, "
        f"set-up phases ended at {run.phases}"
    )


def print_checks(checked: Dict) -> None:
    for name, c in checked.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)

