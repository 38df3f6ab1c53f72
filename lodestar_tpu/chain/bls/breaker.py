"""Device circuit breaker + process-wide degradation record.

The degradation ladder (chain/bls/device_pool.py) keeps individual
verification packs alive through transient device faults; the breaker
handles the *persistent* fault — a wedged XLA runtime, a lost
device — where every device dispatch costs a multi-second failure
before the fallback runs.  After ``failure_threshold`` CONSECUTIVE
device failures the breaker opens and the pool routes packs straight to
the host verifier; after an exponential backoff it half-opens and
admits exactly ONE canary job to the device.  A canary success closes
the breaker (and resets the backoff); a canary failure re-opens it with
the backoff doubled, up to ``max_backoff_s``.

Verification verdicts are NOT failures: an invalid signature returns
``False`` through the normal per-set split and never touches the
breaker — only dispatch *exceptions* (XLA runtime errors, compile
crashes) count.

``note_tier``/``process_degradation`` record the worst degradation tier
any verifier in this process ever engaged.  bench.py stamps the record
into every stage's JSON so a driver round that silently ran on the host
fallback cannot bank a number that looks like device throughput.
"""
from __future__ import annotations

import threading
import time
from typing import Callable

# breaker states (exported as the lodestar_tpu_bls_pool_breaker_state gauge)
CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"
STATE_CODES = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}

FAILURE_THRESHOLD = 3
BASE_BACKOFF_S = 5.0
MAX_BACKOFF_S = 300.0

# degradation tiers, best to worst; ordering is the ladder itself
TIER_DEVICE = "device"
TIER_DEVICE_RETRY = "device_retry"
TIER_PER_SET = "per_set"
TIER_HOST = "host"
_TIER_ORDER = (TIER_DEVICE, TIER_DEVICE_RETRY, TIER_PER_SET, TIER_HOST)


class DeviceCircuitBreaker:
    """Consecutive-failure breaker with exponential half-open backoff.

    Thread-safe: the pool records successes/failures from executor
    threads while the event loop asks for dispatch decisions.
    """

    def __init__(
        self,
        failure_threshold: int = FAILURE_THRESHOLD,
        base_backoff_s: float = BASE_BACKOFF_S,
        max_backoff_s: float = MAX_BACKOFF_S,
        clock: Callable[[], float] = time.monotonic,
    ):
        assert failure_threshold >= 1
        self.failure_threshold = failure_threshold
        self.base_backoff_s = base_backoff_s
        self.max_backoff_s = max_backoff_s
        self._clock = clock
        self._lock = threading.Lock()
        self._state = CLOSED
        self._consecutive_failures = 0
        self._backoff_s = base_backoff_s
        self._open_until = 0.0
        self._canary_in_flight = False
        self._probe_gen = 0  # identity of the current/last canary
        self.trips = 0  # closed/half-open -> open transitions

    @property
    def state(self) -> str:
        # Reviewed exception: guards a one-field read — microseconds,
        # never held across I/O.
        with self._lock:  # lodelint: disable=transitive-blocking
            return self._state

    def allow_device(self) -> str:
        """Dispatch decision for the next job: ``"device"`` (breaker
        closed), ``"canary"`` (half-open probe — caller MUST report the
        outcome via record_success/record_failure), or ``"host"``
        (open, or a canary is already in flight)."""
        now = self._clock()
        # Reviewed exception: pure in-memory state machine — microseconds,
        # never held across I/O, called once per verification job.
        with self._lock:  # lodelint: disable=transitive-blocking
            if self._state == CLOSED:
                return "device"
            if self._state == OPEN and now >= self._open_until:
                self._state = HALF_OPEN
                self._canary_in_flight = False
            if self._state == HALF_OPEN and not self._canary_in_flight:
                self._canary_in_flight = True
                self._probe_gen += 1
                return "canary"
            return "host"

    def record_success(self, probe: bool = False) -> None:
        """Record a device dispatch success.  ``probe=True`` marks the
        outcome of a job that was admitted as the half-open canary —
        ONLY the canary's own outcome may close the breaker.  A
        straggler job that took its "device" decision before the trip
        and succeeds late must not close (or double-admit canaries);
        its success merely clears the closed-state failure streak."""
        # Reviewed exception: counter reset — microseconds, no I/O.
        with self._lock:  # lodelint: disable=transitive-blocking
            self._consecutive_failures = 0
            if probe and self._state == HALF_OPEN:
                # canary came back healthy: full service, backoff reset
                self._state = CLOSED
                self._backoff_s = self.base_backoff_s
                self._canary_in_flight = False

    @property
    def probe_token(self) -> int:
        """Identity of the most recently admitted canary; a caller
        that got "canary" from allow_device() reads this immediately
        (no other canary can be admitted until this one resolves) and
        passes it back to cancel_probe."""
        # Reviewed exception: one-field read — microseconds, no I/O.
        with self._lock:  # lodelint: disable=transitive-blocking
            return self._probe_gen

    def cancel_probe(self, token: int = None) -> None:
        """Release a canary whose job died before any outcome was
        recorded (pool close() mid-probe, an encode fault): the breaker
        stays half-open and the NEXT allow_device() may admit a fresh
        canary — without this the probe slot would be leaked forever
        and every future job would route to the host.  ``token``
        identity-scopes the release: a STALE ex-canary raising late
        (e.g. its post-resolution host verify fails during close())
        must not free a NEWER canary's in-flight slot and admit two
        concurrent probes."""
        # Reviewed exception: one flag write — microseconds, no I/O.
        with self._lock:  # lodelint: disable=transitive-blocking
            if self._state == HALF_OPEN and self._canary_in_flight and (
                token is None or token == self._probe_gen
            ):
                self._canary_in_flight = False

    def record_failure(self, probe: bool = False) -> bool:
        """Record one device dispatch exception; returns True when this
        failure TRIPPED the breaker (closed/half-open -> open).
        ``probe=True`` marks the canary's own outcome: only IT may
        re-open a half-open breaker — a straggler pre-trip job failing
        late (it can hold the device lock through a multi-second
        failure ladder, easily past the backoff) must not re-open,
        double the backoff, or free the canary slot for a second
        concurrent probe."""
        now = self._clock()
        # Reviewed exception: counter + state flip — microseconds, no I/O.
        with self._lock:  # lodelint: disable=transitive-blocking
            self._consecutive_failures += 1
            if probe:
                if self._state == HALF_OPEN:
                    # canary failed: back to open, backoff doubled
                    self._state = OPEN
                    self._canary_in_flight = False
                    self._backoff_s = min(
                        self._backoff_s * 2, self.max_backoff_s
                    )
                    self._open_until = now + self._backoff_s
                    self.trips += 1
                    return True
                return False
            if (
                self._state == CLOSED
                and self._consecutive_failures >= self.failure_threshold
            ):
                self._state = OPEN
                self._open_until = now + self._backoff_s
                self.trips += 1
                return True
            return False


# ---------------------------------------------------------------------------
# process-wide degradation record (read by bench.py)
# ---------------------------------------------------------------------------

_proc_lock = threading.Lock()
_PROCESS = {"worst_tier": TIER_DEVICE, "breaker_state": CLOSED, "breaker_trips": 0}


def note_tier(tier: str) -> None:
    """Record that a verification ran at ``tier``; keeps the worst."""
    # Reviewed exception: one dict compare-and-set — microseconds, no I/O.
    with _proc_lock:  # lodelint: disable=transitive-blocking
        if _TIER_ORDER.index(tier) > _TIER_ORDER.index(_PROCESS["worst_tier"]):
            _PROCESS["worst_tier"] = tier


def note_breaker(state: str, trips: int) -> None:
    # Reviewed exception: two dict writes — microseconds, no I/O.
    with _proc_lock:  # lodelint: disable=transitive-blocking
        _PROCESS["breaker_state"] = state
        _PROCESS["breaker_trips"] = max(_PROCESS["breaker_trips"], trips)


def process_degradation() -> dict:
    """Worst tier + breaker state this process ever saw (bench JSON)."""
    with _proc_lock:
        return dict(_PROCESS)


def reset_process_record() -> None:
    with _proc_lock:
        _PROCESS.update(
            worst_tier=TIER_DEVICE, breaker_state=CLOSED, breaker_trips=0
        )
