"""One run of one benchmark cell.

    python3 -m benchmark --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is one process that holds the chip.  It reads ``BENCHMARK.json``,
finds the cell's configuration (``configs/``), its traffic file
(``traffic/<traffic>.json``) and the generator that file names
(``traffic/<generator>.py``), and one reader per metric
(``metrics/<metric>.py``).  A new cell, configuration or metric is a new
file and a new entry in ``BENCHMARK.json``; nothing here names one.

Order of a run:

1. Worker processes (no JAX) start signing the cell's requests from the
   seed with the benchmark's own reference (``reference/``).
2. The device is checked: the first JAX device must be a TPU, there must
   be as many as the cell asks for, and the Pallas kernels, device
   hash-to-curve and the native library must be on.  Otherwise the run
   exits non-zero and prints no result.
3. The verifier is built as the node builds it,
   ``DeviceBlsVerifier(metrics=BlsPoolMetrics.get())``, and warmed by one
   request that takes the programs the run takes, so that every one of
   them is compiled or loaded before the window opens.  That is the
   set-up.
4. The window: the generator's client drives
   ``verify_signature_sets`` for ``--seconds``.  With ``--trace 1`` the
   profiler records ``TRACE_SECONDS`` of it (see ``trace_start``).  Any
   trace, lowering or compile inside the window ends the run with no
   result.
5. The generator sends its invalid requests through the same verifier.
   Peak device memory is read, the verifier is closed, and every verdict
   is compared with how its request was made; the reference verifies a
   sample of the sets one by one (see ``compare``).

Earlier lines of stdout start with ``bench:``.  The last line of stdout is
the result object; the last lines of stderr give each compared number
beside its limit.

The interface a traffic generator module provides: ``Traffic(params,
seed, config)`` with ``make(pool, on_warmup)``, ``make_checks(job_width)``,
``bind(requests, make_set)``, ``warmup``, ``requests``, ``checks``,
``generate_s``, ``drive(verify, seconds)``, ``after_window(verify)`` and
``reference_sample(records, k)``, and a module-level ``check_set(item)``
that the workers run.
"""
from __future__ import annotations

import argparse
import asyncio
import importlib
import importlib.util
import json
import multiprocessing
import os
import random
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# fixed, inside the checkout: the path is part of what a cache entry is
# found by, and each checkout keeps its own
CACHE_DIR = ROOT / ".jax_cache"
REFERENCE_SAMPLE = 8  # valid sets the reference verifies one by one
# A --trace 1 run records a short slice of the window, without Python calls
# or the programs' HLO.  The TPU tracer records every operation of every
# scan step: on a TPU v5e host, stopping the trace took about 90 s for
# 0.3 s of a scan loop, and minutes per traced second of the verify
# programs, so the slice is short, and stopping overlaps the rest of the
# window and the checks after it.  It starts TRACE_START_S into the window
# (past the first job's host encode) plus a share of TRACE_PHASE_S drawn
# from the seed, so that over runs it falls at every phase of the pool's
# encode-then-device cycle.
TRACE_START_S = 1.0
TRACE_PHASE_S = 1.0
TRACE_SECONDS = 0.2

# the compile-path events JAX reports (jax._src.dispatch); any of them
# inside the window means something was traced or compiled there
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Refused(SystemExit):
    """The run cannot measure what it should: non-zero exit, no result."""

    def __init__(self, why: str):
        super().__init__(f"benchmark refused: {why}")


_T0 = time.monotonic()


def say(what: str, **fields) -> None:
    fields["t"] = round(time.monotonic() - _T0, 3)
    print(f"bench: {what} {json.dumps(fields, sort_keys=True, default=str)}", flush=True)


# ---------------------------------------------------------------------------
# the cell, as BENCHMARK.json and its files describe it
# ---------------------------------------------------------------------------


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _load_file_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object  # the traffic generator module
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, workload: str, bench_file: Path = ROOT / "BENCHMARK.json") -> "Cell":
        bench = json.loads(bench_file.read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise Refused(f"no workload {workload!r} in {bench_file.name}")
        w = cells[workload]
        configs = {c["name"]: c for c in bench["configs"]}
        config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        traffic = json.loads((BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
        generator = importlib.import_module(f"benchmark.traffic.{traffic['generator']}")
        return cls(
            name=workload,
            chips=int(w["chips"]),
            config=config,
            traffic=traffic,
            generator=generator,
            end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
            per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        )


def reader(metric: str) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    mod = _load_file_module(BENCH_DIR / "metrics" / f"{metric}.py",
                            "benchmark.metrics." + metric.replace(".", "_"))
    return mod.read


# ---------------------------------------------------------------------------
# what one run saw, as the metric readers get it
# ---------------------------------------------------------------------------


def counter_snapshot(registry) -> Dict[str, float]:
    """Every sample of a Prometheus registry: name{labels} -> value."""
    out = {}
    for family in registry.collect():
        for s in family.samples:
            labels = ",".join(f"{k}={v}" for k, v in sorted(s.labels.items()))
            out[f"{s.name}{{{labels}}}" if labels else s.name] = s.value
    return out


@dataclass
class Run:
    """Everything a metric reader may read."""

    workload: str
    setup_s: float
    window: dict  # start, end, records, lateness_s (host clock)
    before: Dict[str, float]  # counters when the window opened
    after: Dict[str, float]  # counters when it closed
    trace: Optional[dict] = None  # benchmark.trace.summarize, --trace 1 only

    def delta(self, sample: str) -> float:
        return self.after.get(sample, 0.0) - self.before.get(sample, 0.0)

    def histogram_mean(self, name: str) -> Optional[float]:
        """Mean of the observations a histogram took in the window."""
        n = self.delta(f"{name}_count")
        return self.delta(f"{name}_sum") / n if n else None

    @property
    def answered(self) -> List[dict]:
        return [r for r in self.window["records"] if r["verdict"] is not None]

    @property
    def window_s(self) -> float:
        return self.window["end"] - self.window["start"]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


@dataclass
class System:
    """The verifier a run drives, and how to read it."""

    verifier: object  # has verify_signature_sets and close
    verify_options: Callable  # batchable -> VerifyOptions
    make_set: Callable  # (pk, message, sig) -> SignatureSet
    registry: object  # the Prometheus registry its metrics live in
    device: dict
    job_width: int  # sets per device job when the pool splits a call
    memory_peak: Callable[[], int] = lambda: 0
    degradation: Callable[[], Optional[str]] = lambda: None  # why, or None


def preflight(chips: int) -> dict:
    """The device as JAX reports it; refuses anything but enough TPUs with
    the chip path fully on."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        raise Refused(f"no TPU: JAX sees {device}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees {device}")
    from lodestar_tpu import native
    from lodestar_tpu.ops.bls12_381 import fp, verify

    facts = {
        "pallas": fp._use_pallas(),
        "device_h2c": verify.use_device_h2c(),
        "native": native.available(),
    }
    say("device", jax=jax.__version__, **device, **facts)
    for fact, on in facts.items():
        if on is not True:
            raise Refused(f"{fact} is off on the chip")
    return device


def production_system(device: dict, backend=None) -> System:
    """The verifier as ``blspool serve --verifier device`` and ``dev
    --verifier device`` build it, with every pool default as shipped.
    ``backend`` replaces its device programs (``benchmark/control.py``)."""
    import jax
    from prometheus_client import REGISTRY

    from lodestar_tpu.chain.bls import breaker as brk
    from lodestar_tpu.chain.bls.device_pool import DeviceBlsVerifier, governed_steady_width
    from lodestar_tpu.chain.bls.interface import VerifyOptions
    from lodestar_tpu.chain.bls.metrics import BlsPoolMetrics
    from lodestar_tpu.crypto.bls import api

    def memory_peak() -> int:
        return max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()
        )

    def degradation() -> Optional[str]:
        record = brk.process_degradation()
        faults = REGISTRY.get_sample_value("lodestar_tpu_bls_pool_device_faults_total") or 0
        if record["worst_tier"] != brk.TIER_DEVICE or record["breaker_state"] != brk.CLOSED or faults:
            return f"a job left the device tier: {record}, device faults {faults}"
        return None

    return System(
        verifier=DeviceBlsVerifier(metrics=BlsPoolMetrics.get(), _backend=backend),
        verify_options=lambda batchable: VerifyOptions(batchable=batchable),
        make_set=lambda pk, msg, sig: api.SignatureSet(api.PublicKey(pk), msg, api.Signature(sig)),
        registry=REGISTRY,
        device=device,
        job_width=governed_steady_width(),
        memory_peak=memory_peak,
        degradation=degradation,
    )


# ---------------------------------------------------------------------------
# compile events: set-up split per program, and none inside the window
# ---------------------------------------------------------------------------


class CompileLog:
    """JAX's compile-path events and the persistent cache's hits and misses,
    each stamped with the host clock."""

    def __init__(self):
        self.events: List[tuple] = []  # (t, kind, name, seconds)
        self._lock = threading.Lock()

    def install(self) -> None:
        import jax

        from lodestar_tpu.aot import cache as aot_cache

        def on_duration(event: str, seconds: float, **kwargs) -> None:
            if event in COMPILE_EVENTS:
                self._add(event.rsplit("/", 1)[-1], kwargs.get("fun_name", ""), seconds)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        aot_cache.install_cache_spy(lambda kind, key, s: self._add(f"cache_{kind}", key, s))

    def _add(self, kind: str, name: str, seconds: float) -> None:
        with self._lock:
            self.events.append((time.monotonic(), kind, name, seconds))

    def between(self, t0: float, t1: float) -> List[tuple]:
        with self._lock:
            return [e for e in self.events if t0 <= e[0] <= t1]

    def per_program(self, t0: float, t1: float) -> Dict[str, dict]:
        """Seconds per event kind by function, and cache events by key
        prefix; functions that took under a second in all are left out."""
        out: Dict[str, dict] = {}
        for _, kind, name, seconds in self.between(t0, t1):
            cache = kind.startswith("cache_")
            slot = out.setdefault(name.split("-", 1)[0] if cache else name, {})
            slot[kind] = slot.get(kind, 0.0) + (1 if cache else seconds)
        return {k: v for k, v in out.items() if sum(v.values()) >= 1}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclass
class Outcome:
    result: dict
    checks: Dict[str, dict] = field(default_factory=dict)
    records: List[dict] = field(default_factory=list)  # one per request sent


def compare(traffic, check_set: Callable, records: List[dict], pool) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit.

    ``records`` are the window's requests and those sent after it.

    - ``wrong_verdicts``: requests whose verdict differs from how they were
      made (valid, or tampered with in a way that makes them invalid).
    - ``unanswered``: requests that raised instead of giving a verdict.
    - ``reference_disagreements``: sets on which the plain reference
      (pairing check of one set, ``benchmark.reference``) disagrees with
      how the set was made: every tampered set sent, and a sample of valid
      ones drawn from the seed.  It checks the yardstick itself.
    """
    wrong = sum(
        1 for r in records if r["verdict"] is not None and r["verdict"] != r["expected"]
    )
    unanswered = sum(1 for r in records if r["verdict"] is None)
    sample = traffic.reference_sample(records, REFERENCE_SAMPLE) if records else []
    agree = pool.map(check_set, sample)
    say("compared", requests=len(records), reference_sets=len(sample))
    return {
        "wrong_verdicts": {"value": wrong, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "reference_disagreements": {"value": len(agree) - sum(agree), "limit": 0},
    }


def _passes(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def trace_start(seed: int) -> float:
    """Seconds into the window at which a ``--trace 1`` run starts its
    slice: the same for the same seed."""
    return TRACE_START_S + TRACE_PHASE_S * random.Random(f"trace:{seed}").random()


async def _trace_slice(trace_dir: str, start: float) -> Tuple[float, float]:
    """Record the profiler from ``start`` seconds into the window for
    ``TRACE_SECONDS``; returns the traced seconds (host clock, from when
    recording had started until it was asked to stop) and the seconds that
    stopping took.  The trace marks the slice itself, for ``trace.py``."""
    import jax

    from benchmark import trace as tr

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False  # the programs' HLO is huge
    await asyncio.sleep(start)
    # off the event loop: starting and stopping take a while, and the
    # pool's scheduling must not stall meanwhile
    await asyncio.to_thread(jax.profiler.start_trace, trace_dir, profiler_options=options)
    t0 = time.monotonic()
    # the slice, as a host event on the trace's own clock (trace.SLICE)
    with jax.profiler.TraceAnnotation(tr.SLICE):
        await asyncio.sleep(TRACE_SECONDS)
    t1 = time.monotonic()
    await asyncio.to_thread(jax.profiler.stop_trace)
    return t1 - t0, time.monotonic() - t1


async def _measure(seconds: float, trace_at: Optional[float], system: System, traffic,
                   warm_ready: threading.Event, data_ready: threading.Event,
                   compile_log: Optional[CompileLog], t_start: float) -> dict:
    verifier = system.verifier

    async def verify(sets, batchable: bool) -> bool:
        return await verifier.verify_signature_sets(sets, system.verify_options(batchable))

    # set-up: one request that takes every program the run takes
    await asyncio.to_thread(warm_ready.wait)
    if traffic.warmup is None:
        raise Refused("the generator made no warm-up request")
    t_warm = time.monotonic()
    traffic.bind([traffic.warmup], system.make_set)
    say("warmup start", sets=len(traffic.warmup.bound))
    warm_verdict = await verify(traffic.warmup.bound, traffic.batchable)
    warm_s = time.monotonic() - t_warm
    say("warmup", seconds=warm_s, verdict=warm_verdict, expected=traffic.warmup.valid,
        programs=compile_log.per_program(t_warm, time.monotonic()) if compile_log else {})
    why = system.degradation()
    if why:  # no window is worth measuring on a degraded path
        raise Refused(f"during warm-up, {why}")
    await asyncio.to_thread(data_ready.wait)
    if not traffic.requests:
        raise Refused("the generator made no requests")
    traffic.make_checks(system.job_width)
    traffic.bind(traffic.requests + traffic.checks, system.make_set)
    say("data", requests=len(traffic.requests), sets=sum(len(r.raw) for r in traffic.requests),
        checked_after=len(traffic.checks), job_width=system.job_width,
        generate_s=traffic.generate_s)
    say("window open", seconds=seconds, trace_at=trace_at)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace_at is not None else None
    before = counter_snapshot(system.registry)
    tracer = asyncio.ensure_future(_trace_slice(trace_dir, trace_at)) if trace_dir else None
    setup_s = time.monotonic() - t_start
    window = await traffic.drive(verify, seconds)
    after = counter_snapshot(system.registry)
    in_window = compile_log.between(window["start"], window["end"]) if compile_log else []
    say("window", requests=len(window["records"]), sets=sum(r["sets"] for r in window["records"]),
        seconds=window["end"] - window["start"], lateness_s=window["lateness_s"],
        compiles_in_window=len(in_window))

    t_after = time.monotonic()
    checked = await traffic.after_window(verify)
    say("after window", requests=len(checked), seconds=time.monotonic() - t_after,
        verdicts=[(r["kind"], r["verdict"]) for r in checked],
        compiles=len(compile_log.between(t_after, time.monotonic())) if compile_log else 0)
    trace_summary = None
    if tracer is not None:
        from benchmark import trace as tr

        traced_s, stop_s = await tracer  # stopping overlapped the checks
        t_read = time.monotonic()
        try:
            trace_summary = tr.summarize(trace_dir, traced_s)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        say("trace", traced_s=traced_s, stop_s=stop_s, read_s=time.monotonic() - t_read,
            found=trace_summary is not None)

    memory_peak = system.memory_peak()
    await verifier.close()
    return {
        "setup_s": setup_s, "window": window, "checked": checked, "before": before,
        "after": after, "trace": trace_summary, "in_window": in_window,
        "memory_peak": memory_peak,
    }


def run(cell: Cell, seconds: float, trace_at: Optional[float], system: System, pool, traffic,
        warm_ready: threading.Event, data_ready: threading.Event,
        t_start: float, compile_log: Optional[CompileLog] = None) -> Outcome:
    """Set-up, window, comparison and metrics, on a verifier that is built.
    ``trace_at``: where the traced slice starts, or None for a run with
    ``--trace 0``."""
    trace = trace_at is not None
    m = asyncio.run(_measure(seconds, trace_at, system, traffic, warm_ready, data_ready,
                             compile_log, t_start))
    window = m["window"]
    if m["in_window"]:
        raise Refused(f"traced, lowered or compiled inside the window: {m['in_window']}")
    why = system.degradation()
    if why:
        raise Refused(why)

    records = window["records"] + m["checked"]
    checks = compare(traffic, cell.generator.check_set, records, pool)
    run_ = Run(workload=cell.name, setup_s=m["setup_s"], window=window,
               before=m["before"], after=m["after"], trace=m["trace"])
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for metric in wanted:
        value = reader(metric["name"])(run_)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    device = dict(system.device, memory_peak_bytes=m["memory_peak"])
    result = {
        "correct": _passes(checks),
        "attempted": len(records),
        "failed": sum(1 for r in records if r["verdict"] is None),
        "metrics": metrics,
        "device": device,
    }
    if trace and m["trace"]:
        device["busy_s"] = m["trace"]["busy_s"]
        device["window_s"] = m["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": m["trace"]["device_ops"],
            "idle_gaps": m["trace"]["idle_gaps"],
        }
    result["checks"] = checks
    return Outcome(result=result, checks=checks, records=records)


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            start_system: Callable[[], Tuple[System, Optional[CompileLog]]],
            n_workers: int, t_start: float) -> Outcome:
    """One whole run: the workers sign the traffic while ``start_system``
    checks the device and builds the verifier; then ``run``.  Every worker
    process has ended when this returns or raises."""
    traffic = cell.generator.Traffic(cell.traffic, seed, cell.config)
    warm_ready, data_ready = threading.Event(), threading.Event()
    failure: List[BaseException] = []
    with multiprocessing.get_context("spawn").Pool(n_workers) as pool:
        def generate() -> None:
            try:
                traffic.make(pool, on_warmup=lambda _: warm_ready.set())
            except BaseException as e:  # handed to the main thread below
                failure.append(e)
            finally:
                warm_ready.set()
                data_ready.set()

        gen = threading.Thread(target=generate, name="bench-generate", daemon=True)
        gen.start()
        try:
            system, compile_log = start_system()
            outcome = run(cell, seconds, trace_start(seed) if trace else None, system, pool,
                          traffic, warm_ready, data_ready, t_start, compile_log)
        except Refused:
            if failure:
                raise Refused(f"the generator failed: {failure[0]!r}")
            raise
        finally:
            pool.terminate()
            pool.join()
            gen.join(timeout=5)
    return outcome


def report(outcome: Outcome) -> None:
    for name, c in outcome.checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(outcome.result), flush=True)


def workers() -> int:
    """Signing processes: half the cores.  More starve the warm-up, whose
    tracing is one Python thread (measured on one v5e chip host: 11 of 13
    cores signing stretched it to ~180 s)."""
    return max(1, (os.cpu_count() or 2) // 2)


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> None:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="python3 -m benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell.load(args.workload)
    # before anything imports JAX or the system under test
    os.environ["LODESTAR_TPU_PRESET"] = cell.config["preset"]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

    def start_system() -> Tuple[System, CompileLog]:
        device = preflight(cell.chips)
        compile_log = CompileLog()
        compile_log.install()
        return production_system(device), compile_log

    report(execute(cell, args.seed, args.seconds, bool(args.trace), start_system,
                   workers(), t_start))
