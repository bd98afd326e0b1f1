"""One run of one benchmark cell: the trainer's step, timed or traced, and the
comparison with the plain reference that decides ``correct``.

Everything that belongs to one configuration, traffic mix, cell, layer or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives:

    bench/configs/<config>.json   sizes, as run, with the source's keys
    bench/configs/<config>.py     plain reference: weights from a seed, loss
    bench/flops/<config>.py       model FLOPs of one training step
    bench/traffic/<traffic>.json  batch, sequence, workers, method flags
    bench/workloads/<cell>.json   the limits of the comparison
    bench/layers/<layer>.json     device-op name patterns of one layer
    bench/metrics/<metric>.py     reader of one per-layer metric

The timed path is the trainer as its users run it: the parameters placed
replicated on the data mesh, ``repro.train.step_simple.build_train_step``
with the flags parsed by ``repro.launch.train.build_parser``, and the state
from ``repro.train.state.init_state``. The benchmark makes the weights (one
jitted call from the seed) and the token batches (``traffic.py``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
IN_FLIGHT = 2          # steps dispatched ahead of the oldest unfinished one


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


_MODULES: dict = {}


def load_module(path: pathlib.Path):
    """A module of the benchmark by its file (names may hold '-' and '.'),
    loaded once."""
    path = pathlib.Path(path).resolve()
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def bench_module(name: str):
    return load_module(BENCH / f"{name}.py")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/workloads/<cell>.json "limits"
    reference: object     # bench/configs/<config>.py
    flops: object         # bench/flops/<config>.py

    @property
    def workers(self) -> int:
        return int(self.traffic["data"])

    @property
    def global_batch(self) -> int:
        return self.workers * int(self.traffic["batch_per_worker"])

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch * int(self.traffic["seq_len"])


def load_cell(name: str, bench_dir: pathlib.Path = BENCH) -> tuple:
    """(Cell, the BENCHMARK.json entries of its per-layer metrics)."""
    spec = load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cell = Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(bench_dir.parent / cfg_entry["file"]),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench_dir / "workloads" / f"{name}.json")["limits"],
        reference=load_module(bench_dir / "configs" / f"{w['config']}.py"),
        flops=load_module(bench_dir / "flops" / f"{w['config']}.py"),
    )
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    return cell, per_layer


def require_devices(chips: int, *, platform: str = "tpu"):
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoDevice(f"bench: no {platform.upper()} found: JAX reports "
                       f"platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"bench: the cell asks for {chips} chips, JAX reports "
                       f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, whatever
    the environment says; every program is kept, however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: it reads an access-time file per entry, and one entry
    # written without it stops every later write
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts the programs this process compiled or loaded from the
    persistent cache (``n``), and those loaded (``hits``)."""

    def __init__(self):
        from jax import monitoring

        self.n = self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1
        elif event == CACHE_HIT_EVENT:
            self.hits += 1


def program_seed(seed: int) -> int:
    """The trainer's base seed is a uint32."""
    return int(seed) % (1 << 32)


def program_argv(cell: Cell) -> list:
    t, m = cell.traffic, cell.traffic["method"]
    return ["--arch", cell.config["program"]["arch"], "--full",
            "--batch", str(cell.global_batch), "--seq-len", str(t["seq_len"]),
            "--host-data", str(cell.workers), "--host-model", "1",
            "--compressor", m["compressor"], "--server", m["server"],
            "--budget-kind", m["budget_kind"], "--budget", repr(m["budget"]),
            "--lr", repr(m["lr"]), "--warmup", str(m["warmup"]),
            *t.get("flags", [])]


def build_step(cell: Cell, devices):
    """The trainer's compiled-to-be step for the cell's flags, on a data mesh
    of ``devices``. Returns (model, mesh, step, compression config)."""
    from repro.configs.registry import get_config, trainer_mode
    from repro.core.algorithm import CompressionConfig
    from repro.core.budgets import BudgetConfig
    from repro.dist import collectives
    from repro.launch import train
    from repro.launch.mesh import make_mesh, worker_axes_of
    from repro.models.model import Model
    from repro.train.state import LrSchedule
    from repro.train.step_simple import TrainStepConfig, build_train_step

    args = train.build_parser().parse_args(program_argv(cell))
    if (args.mode or trainer_mode(args.arch)) != "simple":
        raise SystemExit(f"bench: {args.arch} trains in streamed mode; "
                         f"the harness drives the simple trainer")
    cfg = dataclasses.replace(get_config(args.arch),
                              **cell.config["program"]["replace"])
    model = Model(cfg)
    mesh = make_mesh((args.host_data, args.host_model), ("data", "model"),
                     devices=devices)
    comp = CompressionConfig(
        compressor=args.compressor,
        budget=BudgetConfig(kind=args.budget_kind, value=args.budget),
        server=args.server, local_steps=args.tau,
        local_budget=args.local_budget,
        worker_sample_fraction=args.participation)
    ring_rows = ((args.ring_chunk_rows or collectives.DEFAULT_RING_CHUNK_ROWS)
                 if args.ring else None)
    part = None
    if (args.worker_weights is not None or args.quorum_frac is not None
            or args.dropout > 0.0):
        weights = (tuple(float(x) for x in args.worker_weights.split(","))
                   if args.worker_weights else None)
        part = collectives.ParticipationSpec(
            weights=weights, q_frac=args.quorum_frac, dropout=args.dropout)
    step = build_train_step(model, TrainStepConfig(
        compression=comp, lr=LrSchedule(base=args.lr, warmup=args.warmup),
        local_lr=args.local_lr, worker_axes=worker_axes_of(mesh),
        vote_impl=args.vote_impl, quorum=args.quorum, backend=args.backend,
        bucketed=args.bucketed, ring_chunk_rows=ring_rows,
        participation=part), mesh)
    return model, mesh, step, comp


class Program:
    """The trainer under test: its compiled step and its state, built once
    and driven by set-up and window alike."""

    def __init__(self, cell: Cell, devices, seed: int, built=None):
        """``built``: what ``build_step`` returned, to drive it from a new
        seed without building it again."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.train.state import init_state

        model, self.mesh, self.step, comp = built or build_step(cell, devices)
        self.batch_sharding = NamedSharding(self.mesh, P("data"))
        params = make_init(cell, NamedSharding(self.mesh, P()))(
            refalgo().param_key(seed))
        check_layout(params, model.param_shapes())
        self.p0 = host_copy(params)
        self.state = init_state(params, server=comp.server,
                                seed=program_seed(seed), mesh=self.mesh)

    def put(self, batch: dict) -> dict:
        import jax

        return {k: jax.device_put(v, self.batch_sharding) for k, v in batch.items()}


def refalgo():
    return bench_module("refalgo")


def host_copy(tree):
    """The arrays of ``tree`` on the host. The comparison works from these
    copies: weights made again in another program need not be bit for bit
    the ones stored (on the TPU they differ in many coordinates)."""
    import jax

    return jax.device_get(tree)


def make_init(cell: Cell, sharding):
    """The weights of the configuration, made on the device in one jitted
    call from the seed, in the dtypes the configuration states."""
    import functools

    import jax

    return jax.jit(functools.partial(cell.reference.init_params, cfg=cell.config),
                   out_shardings=sharding)


def check_layout(params, shapes) -> None:
    import jax

    got = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree_util.tree_map(lambda x: (tuple(x.shape), str(x.dtype)), shapes)
    if got != want:
        raise SystemExit(f"bench: the configuration's weights do not fit the "
                         f"trainer's parameter tree:\n{got}\nvs\n{want}")


def traffic_gen(cell: Cell, seed: int):
    return bench_module("traffic").Generator(
        cell.traffic, vocab=int(cell.config["vocab_size"]), seed=seed,
        global_batch=cell.global_batch)


# ---------------------------------------------------------------------------
# Set-up, window, trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Snapshots:
    """What one side (trainer or reference) leaves for the comparison: the
    loss of each set-up step and its parameters, on the host, before the
    first step, after it, and after the last."""
    losses: list
    p0: object
    p1: object
    pn: object
    grad_norms: np.ndarray = None   # the reference's: per-leaf |gradient|


@dataclasses.dataclass
class SetupReadings:
    losses: list          # loss of each set-up step
    first_update: np.ndarray   # per-leaf |p0 - p1| / lr0
    change: np.ndarray         # per-leaf |p0 - p_n| after the set-up steps
    first_moves: list          # per leaf, int8 sign(p0 - p1) of every
                               # coordinate, on the host
    change_moved: np.ndarray   # per leaf, coordinates with p_n != p0
    names: list                # per leaf, its path in the parameter tree
    grad_norms: np.ndarray = None   # the reference's: per-leaf |gradient|


def readings_of(snap: Snapshots, cell: Cell, device) -> SetupReadings:
    """Norms and moves of one side's snapshots, worked out on ``device``."""
    import jax

    ra = refalgo()
    m = cell.traffic["method"]
    lr0 = ra.learning_rate(0, m["lr"], m["warmup"])
    with jax.default_device(device):
        p0 = jax.device_put(snap.p0, device)
        p1 = jax.device_put(snap.p1, device)
        first = np.asarray(jax.jit(ra.leaf_norms_from)(p0, p1, 1.0 / lr0))
        moves = [np.asarray(x) for x in
                 jax.tree_util.tree_leaves(jax.jit(ra.leaf_moves_from)(p0, p1))]
        del p1
        pn = jax.device_put(snap.pn, device)
        change = np.asarray(jax.jit(ra.leaf_norms_from)(p0, pn, 1.0))
        change_moved = np.asarray(jax.jit(_leaf_counts_moved)(p0, pn))
    names = [jax.tree_util.keystr(k)
             for k, _ in jax.tree_util.tree_flatten_with_path(snap.p0)[0]]
    return SetupReadings(losses=snap.losses, first_update=first, change=change,
                         first_moves=moves, change_moved=change_moved,
                         names=names, grad_norms=snap.grad_norms)


def _leaf_counts_moved(p0, p):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.count_nonzero(a != b) for a, b in
                      zip(jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(p))])


def run_setup(prog: Program, gen, cell: Cell, step_fn=None) -> Snapshots:
    """The first steps of the run through the window's own call and feed;
    keeps what the comparison needs from the trainer's own state."""
    import jax

    step_fn = step_fn or prog.step
    losses = []
    with jax.sharding.set_mesh(prog.mesh):
        for k in range(int(cell.traffic["setup_steps"])):
            prog.state, metrics = step_fn(prog.state, prog.put(gen.batch(k)))
            losses.append(float(metrics["loss"]))
            if k == 0:
                p1 = host_copy(prog.state.params)
    return Snapshots(losses=losses, p0=prog.p0, p1=p1,
                     pn=host_copy(prog.state.params))


@dataclasses.dataclass
class Window:
    steps: int
    seconds: float
    losses: list
    compiles: int
    longest_wait_s: float     # longest time between two step completions
    full_collections: list    # seconds of each full garbage collection


def run_window(prog: Program, gen, first_step: int, seconds: float,
               counter: CompileCounter, *, annotate: bool = False,
               max_steps: int | None = None, step_fn=None) -> tuple:
    """Whole steps until ``seconds`` have passed (or ``max_steps``), at most
    IN_FLIGHT ahead of the oldest unfinished one; ends on the last step's
    completion. Returns (Window, time of the first dispatch)."""
    import jax

    step_fn = step_fn or prog.step
    span = jax.profiler.TraceAnnotation if annotate else _no_span
    pending, losses, done, full = collections.deque(), [], [], []

    def on_gc(phase, info):
        if info["generation"] == 2:
            full.append(time.perf_counter() if phase == "start"
                        else time.perf_counter() - full.pop())

    gc.callbacks.append(on_gc)
    compiles0 = counter.n
    k = first_step
    with jax.sharding.set_mesh(prog.mesh):
        t0 = time.perf_counter()
        while True:
            with span("bench.make_batch"):
                batch = prog.put(gen.batch(k))
            with span("bench.dispatch"):
                prog.state, metrics = step_fn(prog.state, batch)
            pending.append(metrics["loss"])
            k += 1
            if len(pending) > IN_FLIGHT:
                with span("bench.wait"):
                    losses.append(float(pending.popleft()))
                done.append(time.perf_counter())
            n = k - first_step
            if (max_steps is not None and n >= max_steps) or (
                    max_steps is None and time.perf_counter() - t0 >= seconds):
                break
        with span("bench.wait"):
            jax.block_until_ready(prog.state)
            losses.extend(float(x) for x in pending)
        t1 = time.perf_counter()
    gc.callbacks.remove(on_gc)
    return Window(steps=k - first_step, seconds=t1 - t0, losses=losses,
                  compiles=counter.n - compiles0,
                  longest_wait_s=float(np.max(np.diff([t0, *done, t1]))),
                  full_collections=full), t0


@contextlib.contextmanager
def _no_span(name):
    yield


def peak_bytes(devices) -> int:
    """Peak device memory of the fullest chip: buffers (``peak_bytes_in_use``)
    plus what the runtime reserved for the programs' temporaries
    (``peak_bytes_reserved``; the TPU runtime keeps them apart)."""
    def peak(d):
        s = d.memory_stats() or {}
        return int(s.get("peak_bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in devices)


def traced_window(prog: Program, gen, first_step: int, steps: int,
                  counter: CompileCounter):
    """``steps`` steps under the profiler; returns (Window, trace file)."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    TRACE_DIR.mkdir(parents=True)
    with jax.profiler.trace(str(TRACE_DIR)):
        win, _ = run_window(prog, gen, first_step, 0.0, counter, annotate=True,
                            max_steps=steps)
    files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise SystemExit("bench: the profiler wrote no trace")
    return win, files[-1]


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------

def make_reference(cell: Cell, *, precision="float32", fault=None):
    ra = refalgo()
    m = cell.traffic["method"]
    if (m["compressor"], m["server"], m["budget_kind"]) != (
            "sparsign", "majority_vote", "fixed"):
        raise SystemExit(f"bench: the reference has no method {m}")
    return ra.Reference(cell.reference, cell.config, workers=cell.workers,
                        budget=m["budget"], lr=m["lr"], warmup=m["warmup"],
                        precision=precision, fault=fault)


def run_reference(cell: Cell, gen, seed: int, device, p0, *,
                  precision="float32", fault=None, ref=None) -> Snapshots:
    """The plain reference from the same weights ``p0`` (host arrays), seed
    and batches, on one device (``ref``: one ``make_reference`` made, to use
    again)."""
    import jax

    steps = int(cell.traffic["setup_steps"])
    with jax.default_device(device):
        ref = ref or make_reference(cell, precision=precision, fault=fault)
        params = jax.device_put(p0, device)
        b = int(cell.traffic["batch_per_worker"])
        losses, gnorms = [], None
        for k in range(steps):
            batch = gen.batch(k)
            per_worker = [{n: jax.device_put(v[w * b:(w + 1) * b], device)
                           for n, v in batch.items()} for w in range(cell.workers)]
            params, loss, gn = ref.step(params, per_worker,
                                        program_seed(seed), k)
            losses.append(loss)
            if k == 0:
                gnorms = gn
                p1 = host_copy(params)
        pn = host_copy(params)
    return Snapshots(losses=losses, p0=p0, p1=p1, pn=pn, grad_norms=gnorms)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def run(cell: Cell, per_layer: list, seed: int, seconds: float, trace: bool,
        t_start: float, *, platform: str = "tpu", step_wrapper=None) -> dict:
    """One run of ``cell``; returns the result object. ``step_wrapper`` (tests
    only) puts a broken step in the trainer's place."""
    import jax

    cmp = bench_module("compare")
    devices = require_devices(cell.chips, platform=platform)
    enable_compile_cache()
    counter = CompileCounter()
    prog = Program(cell, devices, seed)
    step_fn = step_wrapper(prog.step) if step_wrapper else prog.step
    gen = traffic_gen(cell, seed)
    log(f"{cell.name}: weights and state made at {time.perf_counter() - t_start:.3f}s")
    got = run_setup(prog, gen, cell, step_fn=step_fn)
    log(f"set-up steps done at {time.perf_counter() - t_start:.3f}s "
        f"({counter.n} programs, {counter.hits} of them from the cache); "
        f"losses {got.losses}")
    # set-up leaves a large heap of long-lived objects (tracing, compiling);
    # collected here, a full collection does not scan it again in the window
    t_gc = time.perf_counter()
    gc.collect()
    gc.freeze()
    log(f"set-up's garbage collected in {time.perf_counter() - t_gc:.3f}s; "
        f"{gc.get_freeze_count()} objects frozen")
    n0 = int(cell.traffic["setup_steps"])
    setup_s = None
    if trace:
        win, trace_file = traced_window(prog, gen, n0, int(cell.traffic["trace_steps"]),
                                        counter)
    else:
        # set-up ends at the first timed dispatch
        win, t0 = run_window(prog, gen, n0, seconds, counter, step_fn=step_fn)
        setup_s = t0 - t_start
    mem = peak_bytes(devices)
    log(f"memory_stats of device 0: {devices[0].memory_stats()}")
    log(f"window: {win.steps} steps in {win.seconds:.3f}s, {win.compiles} programs "
        f"compiled or loaded; peak {mem} B; longest wait between two step "
        f"completions {win.longest_wait_s:.4f}s; full garbage collections "
        f"{[round(x, 4) for x in win.full_collections]}")
    p0 = prog.p0
    del prog
    jax.clear_caches()
    t_ref = time.perf_counter()
    want = run_reference(cell, gen, seed, devices[0], p0)
    log(f"reference: {time.perf_counter() - t_ref:.3f}s; losses {want.losses}")
    got, want = readings_of(got, cell, devices[0]), readings_of(want, cell, devices[0])
    for row in sorted(cmp.leaf_table(got, want),
                      key=lambda r: -(r["first_update_gap"] or 0.0))[:3]:
        log(f"leaf {json.dumps(row)}")
    compared = cmp.compare(got, want, cell.limits)
    failed = sum(1 for x in win.losses if not math.isfinite(x))
    metrics, dev = {}, {**device_info(devices), "memory_peak_bytes": mem}
    result = {"correct": cmp.passed(compared) and failed == 0,
              "attempted": win.steps, "failed": failed}
    if trace:
        tr = bench_module("trace")
        reduced = tr.reduce(trace_file, tr.load_layers(BENCH / "layers"),
                            steps=win.steps)
        ctx = bench_module("metrics_context").Context(
            cell=cell, reduced=reduced, devices=devices)
        for m in per_layer:
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown
    else:
        metrics["tokens_per_s"] = {"value": cell.tokens_per_step * win.steps / win.seconds,
                                   "unit": "tokens/s"}
        metrics["peak_hbm_gib"] = {"value": mem / 2 ** 30, "unit": "GiB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result["metrics"] = metrics
    result["device"] = dev
    result["window_compiles"] = win.compiles
    result["compared"] = compared
    return result
