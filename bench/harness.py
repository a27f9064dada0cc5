"""Runs one benchmark cell: set-up, warm-up, a measured window, the check.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file (``bench/configs/``), its traffic mix
(``bench/traffic/``), one reader per metric (``bench/metrics/``) and one
module per extra check the configuration lists (``bench/checks/``).

A cell declares its deployment in those data files, and the harness
resolves each declaration by the names the program publishes: the
configuration's optional ``"service"`` block names a repartition policy
(a subclass of ``RepartitionPolicy``, by its ``name``), its
``repartition_dt`` and the ``MigrationConfig`` fields; the mix's
``"admission"`` names an ``AdmissionPolicy`` subclass the same way.  What
a cell does not declare is built as the service's defaults.

The system under test is driven only through its public surface:
``JasdaScheduler(slices, policy)``, ``JasdaService(scheduler, arrivals,
config=, admission=).run()``, ``svc.metrics`` and ``scheduler.log``.  The
harness wraps three scheduler methods on the instance to time the layers
(bid collection, pool pack and dispatch, settle) and to keep the inputs and
outputs of the rounds that the output check samples, and wraps the
service's metrics hooks to stamp admissions and awards on the wall clock.

The service runs closed-loop: rounds follow each other at once, and
simulated time advances by ``round_dt`` a round.  The arrival stream is the
benchmark's own (``traffic.JobStream``); the service pulls it once at the
start of every round, which gives the harness the round boundaries and the
place to end the window (:class:`StopWindow` unwinds the service loop).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
OUT_DIR = REPO / ".bench_out"

if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import traffic  # noqa: E402

GIB = float(1 << 30)
#: the far horizon handed to the service: the window, not simulated time,
#: ends a run
HORIZON = 1e9


class StopWindow(Exception):
    """Raised from the arrival pull that closes the measured window."""


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Resolving a cell by name
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(repo: Path = REPO) -> dict:
    with open(repo / "BENCHMARK.json") as f:
        return json.load(f)


def _listed_for(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, repo: Path = REPO) -> Cell:
    bench = load_benchmark(repo)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(repo / cfg_entry["file"]) as f:
        config = json.load(f)
    mix = traffic.load_mix(traffic.mix_path(BENCH, w["traffic"]))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _listed_for(m, name)],
        per_layer=[m for m in bench["per_layer"] if _listed_for(m, name)])


#: where ``check(run)`` of each extra check a configuration lists lives
CHECKS = BENCH / "checks"


def _module_attr(path: Path, attr: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _module_attr(BENCH / "metrics" / f"{name}.py", "read")


def check_reader(name: str):
    """The ``check(run)`` function of ``bench/checks/<name>.py``: it returns
    ``{number: {"value": v, "limit": l}}``, correct where ``v <= l``."""
    path = CHECKS / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in CHECKS.glob("*.py"))
        raise ValueError(f"no check {name!r} in {CHECKS} (have {have})")
    return _module_attr(path, "check")


# ---------------------------------------------------------------------------
# Building the system under test from a configuration and a mix
# ---------------------------------------------------------------------------


def build_slices(config: dict):
    from repro.core import SliceSpec

    out = []
    for group in config["slices"]:
        for i in range(int(group["count"])):
            prefix = group["prefix"].replace("{g}", str(i))
            sid = prefix if "{g}" in group["prefix"] else f"{prefix}{i}"
            out.append(SliceSpec(
                sid, float(group["capacity_gib"]) * GIB,
                n_chips=int(group["n_chips"]),
                flops_per_s=float(config["flops_per_s_per_chip"])))
    return out


def build_policy(config: dict):
    from repro.core.policy import Policy

    presets = {"balanced": Policy, "utilization": Policy.utilization}
    return presets[config["policy"]]()


def named_subclass(base: type, name: str) -> type:
    """The subclass of ``base`` whose own ``name`` attribute is ``name``,
    among those the program has defined; an unknown name raises, naming
    what exists."""
    found: Dict[str, type] = {}
    todo = list(base.__subclasses__())
    while todo:
        cls = todo.pop()
        todo.extend(cls.__subclasses__())
        own = cls.__dict__.get("name")
        if isinstance(own, str) and found.setdefault(own, cls) is not cls:
            raise ValueError(f"two {base.__name__} subclasses are named "
                             f"{own!r}: {found[own].__name__}, {cls.__name__}")
    if name not in found:
        raise ValueError(f"unknown {base.__name__} {name!r} "
                         f"(have {sorted(found)})")
    return found[name]


def build_admission(mix: dict):
    """The mix's ``{"policy": <name>, **params}`` as an AdmissionPolicy."""
    from repro.service.admission import AdmissionPolicy

    params = dict(mix["admission"])
    return named_subclass(AdmissionPolicy, params.pop("policy"))(**params)


def service_components(config: dict) -> dict:
    """The ``ServiceConfig`` fields the configuration's ``"service"`` block
    declares: ``repartition`` ({"policy": <name>, "params": {...}}),
    ``repartition_dt`` and ``migration`` (the ``MigrationConfig`` fields).
    No block, or a key left out, keeps the service's default (None)."""
    from repro.core.repartition import MigrationConfig, RepartitionPolicy

    block = dict(config.get("service", {}))
    out = {}
    if "repartition" in block:
        decl = dict(block.pop("repartition"))
        cls = named_subclass(RepartitionPolicy, decl.pop("policy"))
        out["repartition"] = cls(**decl.pop("params", {}))
        if decl:
            raise ValueError(f"unknown keys {sorted(decl)} in "
                             "service.repartition (have policy, params)")
    if "repartition_dt" in block:
        out["repartition_dt"] = float(block.pop("repartition_dt"))
    if "migration" in block:
        out["migration"] = MigrationConfig(**block.pop("migration"))
    if block:
        raise ValueError(f"unknown keys {sorted(block)} in the service block "
                         "(have repartition, repartition_dt, migration)")
    return out


def seeds(seed: int) -> Dict[str, np.random.SeedSequence]:
    """Independent streams for the jobs, the executor noise and the check."""
    root = np.random.SeedSequence(int(seed) & ((1 << 128) - 1))
    jobs, execution, check = root.spawn(3)
    return {"jobs": jobs, "exec": execution, "check": check}


# ---------------------------------------------------------------------------
# Instrumentation: layer spans, wall-clock job stamps, sampled rounds
# ---------------------------------------------------------------------------


class Spans:
    """Host seconds spent in wrapped scheduler methods while the window is
    open; with tracing on each call is also a ``TraceAnnotation`` named
    ``bench.<span>``, so device gaps can be attributed to it."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.open = False
        self.seconds: Dict[str, float] = {}

    def wrap(self, obj, method: str, span: str, before=None, after=None):
        orig = getattr(obj, method)
        self.seconds.setdefault(span, 0.0)
        annotation = None
        if self.annotate:
            from jax.profiler import TraceAnnotation

            annotation = TraceAnnotation

        def wrapped(*args, **kw):
            token = before(*args) if before is not None else None
            t0 = time.perf_counter()
            if annotation is not None and self.open:
                with annotation(f"bench.{span}"):
                    out = orig(*args, **kw)
            else:
                out = orig(*args, **kw)
            dt = time.perf_counter() - t0
            if self.open:
                self.seconds[span] += dt
            if after is not None:
                after(token, args, out)
            return out

        setattr(obj, method, wrapped)


class JobClock:
    """Wall-clock admission and first-award stamps, plus the simulated
    admission and award times the reference's age term needs."""

    def __init__(self, metrics):
        self.admit_wall: Dict[str, float] = {}
        self.award_wall: Dict[str, float] = {}
        self.last_selected: Dict[str, float] = {}
        self.dropped_unawarded: set = set()
        admitted, awarded, dropped = (metrics.admitted, metrics.awarded,
                                      metrics.dropped)

        def on_admitted(job_id, now):
            self.admit_wall[job_id] = time.perf_counter()
            self.last_selected.setdefault(job_id, now)
            return admitted(job_id, now)

        def on_awarded(job_id, now):
            self.award_wall.setdefault(job_id, time.perf_counter())
            self.last_selected[job_id] = now
            return awarded(job_id, now)

        def on_dropped(job_id):
            if job_id not in self.award_wall:
                self.dropped_unawarded.add(job_id)
            return dropped(job_id)

        metrics.admitted = on_admitted
        metrics.awarded = on_awarded
        metrics.dropped = on_dropped


class RoundSampler:
    """Keeps the rounds the output check compares: a reservoir of ``k``
    rounds drawn from the seed, and the round with the most bids."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k = k
        self.rng = rng
        self.n = 0
        self.reservoir: List[dict] = []
        self.largest: Optional[dict] = None

    def offer(self, n_bids: int) -> Optional[int]:
        """Slot for the next round (-1: only as the largest), or None."""
        self.n += 1
        slot = None
        if len(self.reservoir) < self.k:
            slot = len(self.reservoir)
            self.reservoir.append({})
        else:
            j = int(self.rng.integers(0, self.n))
            if j < self.k:
                slot = j
        if slot is None and (self.largest is None
                             or n_bids > self.largest["n_bids"]):
            slot = -1
        return slot

    def keep(self, slot: int, record: dict) -> None:
        if slot >= 0:
            self.reservoir[slot] = record
        if self.largest is None or record["n_bids"] > self.largest["n_bids"]:
            self.largest = record

    def rounds(self) -> List[dict]:
        out = [r for r in self.reservoir if r]
        if self.largest is not None and all(r is not self.largest for r in out):
            out.append(self.largest)
        return out


# ---------------------------------------------------------------------------
# The measured run
# ---------------------------------------------------------------------------


class CompileCounter:
    """Backend compile seconds and count, from ``jax.monitoring``."""

    _instance = None

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax.monitoring as mon

            cls._instance = cls()
            mon.register_event_duration_secs_listener(cls._instance._on)
        return cls._instance

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < chips):
        raise NoAccelerator(
            f"JAX found {len(devices)} {dev.platform} device(s); the cell "
            f"needs {chips} TPU chip(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": min(len(devices), chips) if require_tpu else len(devices)}


def memory_peak(chips: int) -> int:
    import jax

    peak = 0
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


@dataclass
class Run:
    """What one run leaves for the metric readers and the output check."""

    cell: Cell
    seed: int
    seconds_asked: float
    setup_s: float = 0.0
    window_s: float = 0.0
    rounds: int = 0
    spans: Optional[Spans] = None
    clock: Optional[JobClock] = None
    window_open_wall: float = 0.0
    window_close_wall: float = 0.0
    attempted: int = 0
    failed: int = 0
    compiles_in_window: int = 0
    round_log: list = field(default_factory=list)
    sampled: List[dict] = field(default_factory=list)
    jobs: Dict[str, "traffic.JobDraw"] = field(default_factory=dict)
    trace: Optional[object] = None
    trace_error: str = ""
    #: seconds from process start at which each phase of set-up ended
    setup_marks: Dict[str, float] = field(default_factory=dict)
    #: wall seconds of each round of the window, in order
    round_walls: List[float] = field(default_factory=list)
    #: Python garbage collections in the window: {generation: [count, s]}
    gc_pauses: Dict[int, list] = field(default_factory=dict)
    #: the system under test, for extra checks that read its logs
    scheduler: Optional[object] = None
    service: Optional[object] = None

    def latencies(self) -> List[float]:
        """Admit → first award wall seconds of every job admitted in the
        window; a job still unawarded at the close counts with its wait."""
        clock, lo, hi = self.clock, self.window_open_wall, self.window_close_wall
        out = []
        for job, t_adm in clock.admit_wall.items():
            if not lo <= t_adm <= hi:
                continue
            t_aw = clock.award_wall.get(job)
            out.append((t_aw if t_aw is not None and t_aw <= hi else hi) - t_adm)
        return out


def quantile(values: List[float], q: float) -> float:
    """The q-quantile by linear interpolation between order statistics."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def build_system(cell: Cell, streams: dict, on_pull):
    """The scheduler, the service and the job stream of one run."""
    from repro.core import JasdaScheduler
    from repro.service import JasdaService, ServiceConfig

    mix = cell.mix
    sched = JasdaScheduler(build_slices(cell.config), build_policy(cell.config))
    stream = traffic.JobStream(mix, np.random.default_rng(streams["jobs"]),
                               on_pull=on_pull)
    svc = JasdaService(
        sched, stream,
        config=ServiceConfig(round_dt=float(mix["round_dt"]), t_end=HORIZON,
                             seed=int(streams["exec"].generate_state(1)[0]),
                             **service_components(cell.config)),
        admission=build_admission(mix))
    return sched, svc, stream


def warm_score_kernel(pool_sizes) -> None:
    """Compile the score dispatch for every pool size the mix lists, with
    the operands the scheduler packs (one calibrated utility column, four
    system features, no safety grid), so no bucket compiles in the window."""
    from repro.kernels.jasda_score.ops import score_variants

    for m in pool_sizes:
        z1 = np.zeros((int(m), 1), np.float64)
        score_variants(z1, np.zeros((int(m), 4)), np.ones(1), np.ones(4),
                       z1, z1, lam=0.5, capacity=1.0, theta=1.0, trim=False)


def drive(cell: Cell, seed: int, seconds: float, *, trace: bool,
          t_proc0: float, require_tpu: bool = True,
          trace_dir: Optional[Path] = None) -> Run:
    """Set up, warm up, measure ``seconds`` of rounds; returns the Run."""
    run = Run(cell=cell, seed=seed, seconds_asked=seconds)
    streams = seeds(seed)
    mix = cell.mix
    counter = CompileCounter.get()
    spans = Spans(annotate=trace)
    run.spans = spans

    state = {"open": False, "t0": 0.0, "compiles0": 0}
    warmup = float(mix["warmup_rounds"]) * float(mix["round_dt"])

    def on_pull(now: float) -> None:
        wall = time.perf_counter()
        if not state["open"]:
            if now >= warmup - 1e-9:
                state["open"] = True
                spans.open = True
                if trace:
                    import jax

                    # host TraceMe spans of level 1 (the bench annotations)
                    # only: the Python tracer would log every call of the
                    # host path, and level 2 every runtime event
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(str(trace_dir),
                                             profiler_options=opts)
                    wall = time.perf_counter()
                state["t0"] = state["last"] = wall
                state["compiles0"] = counter.count
                state["n_jobs0"] = stream.n_emitted
                run.window_open_wall = wall
                run.setup_s = wall - t_proc0
            return
        run.rounds += 1
        run.round_walls.append(wall - state["last"])
        state["last"] = wall
        if wall - state["t0"] >= seconds:
            run.window_close_wall = wall
            state["n_jobs1"] = stream.n_emitted
            raise StopWindow

    if require_tpu:
        warm_score_kernel(mix.get("warm_pool_sizes", ()))
    run.setup_marks["kernels_warm"] = time.perf_counter() - t_proc0
    sched, svc, stream = build_system(cell, streams, on_pull)
    run.jobs = stream.jobs
    run.scheduler, run.service = sched, svc
    clock = JobClock(svc.metrics)
    run.clock = clock

    sampler = RoundSampler(int(mix.get("check_rounds", 3)),
                           np.random.default_rng(streams["check"]))
    trust = sched.calibrator

    def before_settle(prep):
        if not spans.open or not getattr(prep, "fit", None):
            return None
        slot = sampler.offer(len(prep.fit))
        if slot is None:
            return None
        jobs = {v.job_id for v in prep.fit}
        return slot, {
            "now": float(prep.now),
            "windows": list(prep.windows),
            "fit": prep.fit,
            "win_idx": np.asarray(prep.win_idx).copy(),
            "budget": dict(prep.budget),
            "trust": {j: (trust.rho(j), trust.hist_avg(j)) for j in jobs},
            "last_selected": {j: clock.last_selected[j] for j in jobs},
            "n_bids": len(prep.fit),
        }

    def after_settle(token, args, rr):
        if token is None:
            return
        slot, record = token
        prep = args[0]
        handle = getattr(prep, "handle", None)
        record["scores"] = (np.asarray(handle.result(), np.float64).copy()
                            if handle is not None
                            else np.zeros(0, np.float64))
        record["result"] = rr
        sampler.keep(slot, record)

    spans.wrap(sched, "_collect_bids", "bids")
    spans.wrap(sched, "_finalize_prep", "pack")
    spans.wrap(sched, "_settle_round", "settle", before=before_settle,
               after=after_settle)

    gc_start = {}

    def on_gc(phase, info):
        if not spans.open:
            return
        if phase == "start":
            gc_start["t"] = time.perf_counter()
        elif "t" in gc_start:
            tally = run.gc_pauses.setdefault(info["generation"], [0, 0.0])
            tally[0] += 1
            tally[1] += time.perf_counter() - gc_start.pop("t")

    gc.callbacks.append(on_gc)
    try:
        svc.run(t_end=HORIZON)
    except StopWindow:
        pass
    finally:
        gc.callbacks.remove(on_gc)
        spans.open = False
        if trace and state["open"]:
            import jax

            jax.profiler.stop_trace()
    if not state["open"] or run.window_close_wall == 0.0:
        raise RuntimeError("the service stopped before the window closed")
    run.window_s = run.window_close_wall - run.window_open_wall
    run.compiles_in_window = counter.count - state["compiles0"]
    run.round_log = [r for r in sched.log if r.t >= warmup - 1e-9]
    run.sampled = sampler.rounds()
    # attempted: jobs that arrived in the window; failed: refused, or gone
    # (deadline, cancel) before a first award
    arrived_in_window = list(stream.jobs)[state["n_jobs0"]:state["n_jobs1"]]
    refused = [j for j in arrived_in_window if j not in clock.admit_wall]
    gone = [j for j in arrived_in_window if j in clock.dropped_unawarded]
    run.attempted = len(arrived_in_window)
    run.failed = len(refused) + len(gone)
    return run


# ---------------------------------------------------------------------------
# The output check
# ---------------------------------------------------------------------------


def check_outputs(run: Run, control: bool = False) -> dict:
    """Compare the sampled rounds with the plain reference (bench/reference);
    with ``control`` the reference in bfloat16 stands in for the program's
    scores.  The numbers of each extra check the configuration lists
    (``"checks"``) join them, control or not: they read the run itself."""
    jobs = {j: reference.JobMemory.from_draw(d.steady_bytes, run.cell.mix)
            for j, d in run.jobs.items()}
    out = reference.check_rounds(run.sampled, run.cell.config, jobs,
                                 control=control)
    for name in run.cell.config.get("checks", ()):
        for key, c in check_reader(name)(run).items():
            if key in out:
                raise ValueError(f"check {name!r} reports {key!r}, which "
                                 "another check already reports")
            out[key] = {"value": c["value"], "limit": c["limit"]}
    return out


# ---------------------------------------------------------------------------
# One run, end to end: the result line
# ---------------------------------------------------------------------------


def _spread(xs: List[float]) -> List[float]:
    """[least, mean, most] of a per-round count."""
    return [min(xs), sum(xs) / len(xs), max(xs)] if xs else []


@dataclass
class Context:
    """What a metric reader sees (``bench/metrics/<name>.py: read(ctx)``)."""

    run: Run
    device: dict
    trace: Optional[object] = None
    peaks: Optional[dict] = None


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *,
            t_proc0: float, require_tpu: bool = True) -> dict:
    """One run of a cell; returns the result object (the last stdout line)."""
    t_imported = time.perf_counter() - t_proc0
    dev = device_info(cell.chips, require_tpu)
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    t_devices = time.perf_counter() - t_proc0
    trace_dir = OUT_DIR / "trace" / cell.name
    if trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
    run = drive(cell, seed, seconds, trace=trace, t_proc0=t_proc0,
                require_tpu=require_tpu, trace_dir=trace_dir)
    run.setup_marks.update(imported=t_imported, devices=t_devices)
    dev["memory_peak_bytes"] = memory_peak(cell.chips)
    ctx = Context(run=run, device=dev)
    if trace:
        import roofline
        import tracereduce

        try:
            ctx.trace = tracereduce.load(trace_dir, run.window_s)
        except tracereduce.TraceError as e:
            run.trace_error = str(e)
        if ctx.trace is not None:
            dev["busy_s"] = ctx.trace.busy_s
            dev["window_s"] = ctx.trace.window_s
        ctx.peaks = roofline.peaks_for(dev["kind"]) if require_tpu else None
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = check_outputs(run)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": bool(correct), "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": dev}
    if trace and ctx.trace is not None:
        result["breakdown"] = ctx.trace.breakdown()
    result["checks"] = checks
    diag = {"rounds": run.rounds, "window_s": run.window_s,
            "setup_s": run.setup_s, "setup_marks": run.setup_marks,
            "longest_rounds_s": sorted(run.round_walls)[-5:],
            "gc_pauses": run.gc_pauses,
            "compiles_in_window":
                run.compiles_in_window,
            "bids_per_round": _spread([r.n_bids for r in run.round_log]),
            "windows_per_round": _spread([r.n_windows
                                          for r in run.round_log]),
            "conflicts_per_round": _spread([r.n_conflicts
                                            for r in run.round_log]),
            "selected_per_round": _spread([r.n_selected
                                           for r in run.round_log]),
            "backends": sorted({(r.score_backend, r.settle_backend)
                                for r in run.round_log}),
            "sampled_rounds": [r["now"] for r in run.sampled],
            "trace_error": run.trace_error}
    if ctx.trace is not None:
        diag["trace_events"] = len(ctx.trace.events)
        diag["trace_spans"] = len(ctx.trace.spans)
        diag["idle_by_span"] = ctx.trace.idle_by_span()
    print("diag " + json.dumps(diag), file=sys.stderr, flush=True)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return result
