"""Prometheus metrics for the BLS verifier pool.

Mirrors the reference's blsThreadPool metric family
(packages/beacon-node/src/metrics/metrics/lodestar.ts:440-510), feeding the
same dashboard shapes (dashboards/lodestar_bls_thread_pool.json).
"""
from __future__ import annotations

from prometheus_client import Counter, Gauge, Histogram, REGISTRY


class BlsPoolMetrics:
    _instance = None

    def __init__(self, registry=REGISTRY):
        ns = "lodestar_tpu_bls_pool"
        self.job_queue_length = Gauge(
            f"{ns}_queue_length", "Signature sets buffered awaiting a batch", registry=registry
        )
        self.jobs_started = Counter(
            f"{ns}_jobs_started_total", "Device verification jobs launched", registry=registry
        )
        self.sig_sets_total = Counter(
            f"{ns}_sig_sets_total", "Signature sets verified", registry=registry
        )
        self.batch_retries = Counter(
            f"{ns}_batch_retries_total",
            "Batches that failed and fell back to per-set verification",
            registry=registry,
        )
        self.invalid_sets = Counter(
            f"{ns}_invalid_sig_sets_total", "Individual sets that failed", registry=registry
        )
        self.job_wait_time = Histogram(
            f"{ns}_job_wait_time_seconds",
            "Time a set waits in the batching buffer",
            buckets=(0.001, 0.005, 0.02, 0.05, 0.1, 0.2, 0.5, 1, 2),
            registry=registry,
        )
        self.job_run_time = Histogram(
            f"{ns}_job_run_time_seconds",
            "Device kernel wall time per job",
            buckets=(0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1, 2, 5),
            registry=registry,
        )
        self.encode_time = Histogram(
            f"{ns}_encode_time_seconds",
            "Host encode stage wall time per job (expand_message_xmd + "
            "field-draw reduction + limb packing; overlaps device "
            "execution of the previous job)",
            buckets=(0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1),
            registry=registry,
        )
        # AOT compile-lifecycle observability (lodestar_tpu/aot): XLA
        # compile times and persistent-cache traffic seen by THIS
        # process, plus warm-manifest freshness at pool construction —
        # a cold first-verify is visible before it costs a slot.
        self.compile_time = Histogram(
            f"{ns}_xla_compile_seconds",
            "XLA compile wall time per program (persistent-cache misses)",
            buckets=(1, 5, 15, 60, 300, 900, 1800, 3600),
            registry=registry,
        )
        self.persistent_cache_hits = Counter(
            f"{ns}_persistent_cache_hits_total",
            "Compiled programs loaded from the persistent cache",
            registry=registry,
        )
        self.persistent_cache_misses = Counter(
            f"{ns}_persistent_cache_misses_total",
            "Programs the persistent cache did not hold (cold compile)",
            registry=registry,
        )
        self.executable_store_events = Counter(
            f"{ns}_executable_store_events_total",
            "Executable-store events (aot/exec_store.py) by kind: "
            "exec_hit (loaded, nothing traced), exec_miss, exec_put, "
            "exec_load_error (removed and recompiled)",
            labelnames=("kind",),
            registry=registry,
        )
        self.warm_manifest_fresh = Gauge(
            f"{ns}_warm_manifest_fresh",
            "1 if every AOT-registered program was warm at pool start "
            "(manifest fresh for this backend/jax/source)",
            registry=registry,
        )
        self.warm_programs_total = Gauge(
            f"{ns}_warm_programs_registered",
            "AOT-registered programs for this node's dispatch set",
            registry=registry,
        )
        self.warm_programs_warm = Gauge(
            f"{ns}_warm_programs_warm",
            "AOT-registered programs present + fresh at pool start",
            registry=registry,
        )
        # Fault-domain observability (chain/bls/breaker.py + the
        # degradation ladder in device_pool.py): a node quietly serving
        # verdicts off the host fallback must be visible on a dashboard,
        # not discovered in a post-mortem.
        self.device_faults = Counter(
            f"{ns}_device_faults_total",
            "Device dispatch exceptions (XLA runtime/compile errors; "
            "verification verdicts of False are NOT counted here)",
            registry=registry,
        )
        self.degraded_jobs = Counter(
            f"{ns}_degraded_jobs_total",
            "Jobs that engaged a degradation tier beyond the batch "
            "kernel (tier: device_retry | per_set | host)",
            labelnames=("tier",),
            registry=registry,
        )
        self.breaker_state = Gauge(
            f"{ns}_breaker_state",
            "Device circuit-breaker state (0 closed / 1 half-open / 2 open)",
            registry=registry,
        )
        self.breaker_trips = Counter(
            f"{ns}_breaker_trips_total",
            "Circuit-breaker trips (closed/half-open -> open)",
            registry=registry,
        )
        self.breaker_probes = Counter(
            f"{ns}_breaker_probes_total",
            "Half-open canary jobs admitted to the device",
            registry=registry,
        )
        self.breaker_short_circuits = Counter(
            f"{ns}_breaker_short_circuited_jobs_total",
            "Jobs routed straight to the host verifier while the "
            "breaker was open",
            registry=registry,
        )
        self.persistent_cache_load_errors = Counter(
            f"{ns}_persistent_cache_load_errors_total",
            "Persistent-cache entries that existed but failed to "
            "deserialize (quarantined + recompiled; see docs/AOT.md)",
            registry=registry,
        )

    @classmethod
    def get(cls) -> "BlsPoolMetrics":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance
