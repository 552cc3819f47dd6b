"""Serving-grade resilience: fault injection, degradation ladder, crash-safe
persisted state (DESIGN.md §14).

The planner's optimality story (layout DP, stack fusion, int8 boundaries)
silently assumes every plan that prices well also *executes* well.  In a
serving process that assumption breaks three ways: a kernel can fail at
execution time (VMEM-bound stack shapes, interpreter edge cases), a batch
can come back non-finite (int8 numerics, bad weights), and the persisted
plan/threshold state can be torn by a mid-write crash.  This module holds
the machinery the serving driver (``launch.cnn_serve``) wires in:

  * ``FaultInjector`` — a deterministic, seeded harness that injects kernel
    exceptions, NaN outputs, and artificial slow steps at configurable
    per-site rates, and corrupts persisted JSON on request.  Every injected
    fault is counted, so tests and CI can assert on exact incident totals.
  * ``degradation_ladder`` — the ordered list of execution variants
    (``Rung``: impl × stack policy × dtype policy) a guarded server walks
    down when a batch fails: pallas+stacks → pallas stacks-off →
    mixed→uniform dtype → decomposed XLA.  Every rung maps to a
    ``PlanCache`` key (never an ad-hoc replan), so the fallback plan is the
    same plan the planner would have produced for that variant.
  * ``IncidentLog`` — the taxonomy (``kernel_fault`` / ``nonfinite`` /
    ``quarantine`` / ``requeue`` / ``corrupt_state`` / ``straggler`` /
    ``degraded``) counted across the server's lifetime and surfaced in
    ``report_lines()``.
  * crash-safe JSON persistence — ``atomic_json_dump`` (payload checksum +
    fsync-before-replace: a mid-write crash never loses the previous
    generation), ``load_json_guarded`` (schema/checksum validation; an
    unreadable file is renamed aside as ``*.corrupt`` and the caller
    rebuilds instead of raising), ``quarantine_file``.

Nothing here imports the serving or CNN stacks — the ladder and the
injector are plain data/state machines, so the training side can reuse
them (``runtime.fault_tolerance`` already shares ``StragglerWatchdog``
in the other direction).
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger("repro.resilience")

CHECKSUM_FIELD = "checksum"


class InjectedKernelFault(RuntimeError):
    """A fault-injection kernel exception (stands in for a real execution
    failure: VMEM OOM in a stack kernel, interpreter crash, device loss)."""


class ServingFault(RuntimeError):
    """Every rung of the degradation ladder failed for one batch.  The
    in-flight requests have been re-queued (front of the queue, original
    order) before this is raised — nothing is lost, the step just did not
    complete."""


# ---------------------------------------------------------------------------
# deterministic fault injection
# ---------------------------------------------------------------------------

class FaultInjector:
    """Seeded, per-site Bernoulli fault injection.

    ``rates`` maps site names to firing probabilities in [0, 1].  A site is
    a fault kind (``"kernel"``, ``"nan"``, ``"slow"``) optionally qualified
    as ``"kind@qualifier"`` — the serving driver passes the executing rung's
    name / dtype policy / impl as qualifiers, so ``{"nan@mixed": 1.0}``
    poisons only the mixed-dtype path while ``{"kernel": 0.1}`` hits every
    rung.  The most specific matching rate wins (first qualifier in the
    caller's order, then the bare kind).

    Determinism: each site key draws from its own ``np.random.Generator``
    seeded by (seed, site key), so the fire/no-fire sequence per site is a
    pure function of the seed and that site's call count — independent of
    how other sites interleave.  Two runs with the same seed and the same
    per-site call sequence inject identical faults.
    """

    def __init__(self, seed: int = 0,
                 rates: Optional[Dict[str, float]] = None,
                 slow_s: float = 0.05):
        self.seed = seed
        self.rates = dict(rates or {})
        for site, r in self.rates.items():
            if not 0.0 <= r <= 1.0:
                raise ValueError(f"rate for {site!r} must be in [0,1], "
                                 f"got {r}")
        self.slow_s = slow_s
        self.counts: Dict[str, int] = {}       # fired, by resolved site key
        self.draws: Dict[str, int] = {}        # total draws, by site key
        self._rngs: Dict[str, np.random.Generator] = {}

    @property
    def fired(self) -> int:
        return sum(self.counts.values())

    def _resolve(self, kind: str,
                 quals: Sequence[str]) -> Optional[Tuple[str, float]]:
        for q in quals:
            key = f"{kind}@{q}"
            if key in self.rates:
                return key, self.rates[key]
        if kind in self.rates:
            return kind, self.rates[kind]
        return None

    def _rng(self, key: str) -> np.random.Generator:
        if key not in self._rngs:
            digest = hashlib.sha256(f"{self.seed}:{key}".encode()).digest()
            self._rngs[key] = np.random.default_rng(
                int.from_bytes(digest[:8], "little"))
        return self._rngs[key]

    def fire(self, kind: str, quals: Sequence[str] = ()) -> bool:
        """Deterministic Bernoulli draw for ``kind`` under ``quals``; counts
        the draw and (when it fires) the incident."""
        hit = self._resolve(kind, quals)
        if hit is None:
            return False
        key, rate = hit
        self.draws[key] = self.draws.get(key, 0) + 1
        if rate <= 0.0:
            return False
        fired = rate >= 1.0 or bool(self._rng(key).random() < rate)
        if fired:
            self.counts[key] = self.counts.get(key, 0) + 1
        return fired

    # -- the three execution-time sites --------------------------------------

    def maybe_kernel_fault(self, quals: Sequence[str] = ()) -> None:
        """Raises ``InjectedKernelFault`` when the kernel site fires."""
        if self.fire("kernel", quals):
            raise InjectedKernelFault(
                f"injected kernel fault (site=kernel, quals={list(quals)})")

    def maybe_slow(self, quals: Sequence[str] = ()) -> float:
        """Sleeps ``slow_s`` when the slow site fires; returns the injected
        delay (0.0 when it did not fire) so callers can log it."""
        if self.fire("slow", quals):
            time.sleep(self.slow_s)
            return self.slow_s
        return 0.0

    def maybe_poison(self, y: np.ndarray,
                     quals: Sequence[str] = ()) -> np.ndarray:
        """Returns ``y`` with its first element overwritten by NaN when the
        nan site fires (the cheap-finite-check must catch it downstream)."""
        if self.fire("nan", quals) and y.size:
            y = np.array(y, dtype=np.float32, copy=True)
            y.flat[0] = np.nan
        return y

    # -- persisted-state corruption (test/CI harness side) -------------------

    @staticmethod
    def corrupt_json(path: str, mode: str = "truncate") -> str:
        """Corrupt a persisted JSON file in place.  Modes:

        * ``truncate``  — cut the file mid-payload (torn write);
        * ``garbage``   — overwrite with non-JSON bytes;
        * ``version``   — bump the schema version to an unknown value;
        * ``checksum``  — flip payload bytes under a stale checksum.
        """
        if mode == "truncate":
            size = os.path.getsize(path)
            with open(path, "r+b") as f:
                f.truncate(max(size // 2, 1))
        elif mode == "garbage":
            with open(path, "wb") as f:
                f.write(b"\x00\xffnot json {]")
        elif mode == "version":
            with open(path) as f:
                obj = json.load(f)
            obj["version"] = 999999
            with open(path, "w") as f:
                json.dump(obj, f)
        elif mode == "checksum":
            with open(path) as f:
                obj = json.load(f)
            if CHECKSUM_FIELD not in obj:
                raise ValueError(f"{path} carries no checksum to violate")
            # mutate the payload without refreshing the checksum
            obj["_tampered"] = True
            with open(path, "w") as f:
                json.dump(obj, f)
        else:
            raise ValueError(f"unknown corruption mode {mode!r}")
        return path


def parse_inject_spec(spec: str, seed: int = 0,
                      slow_s: float = 0.05) -> Optional[FaultInjector]:
    """CLI front end: ``"kernel=0.1,nan@mixed=1.0,slow=0.05"`` -> injector.
    Empty/None spec returns None (injection disabled)."""
    if not spec:
        return None
    rates: Dict[str, float] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        site, _, rate = part.partition("=")
        if not rate:
            raise ValueError(f"--inject entry {part!r} is not site=rate")
        rates[site.strip()] = float(rate)
    return FaultInjector(seed=seed, rates=rates, slow_s=slow_s)


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Rung:
    """One execution variant of the fused serving stack.  ``(policy,
    stack)`` are PlanCache key dimensions — every rung's plan is the
    planner's own plan for that variant, pulled from (or planned once into)
    the cache, never an ad-hoc replan."""
    name: str
    impl: str                     # "pallas" | "xla"
    stack: str                    # stack_policy: "auto" | "off"
    policy: str                   # dtype policy: "uniform" | "mixed"

    @property
    def plan_key(self) -> Tuple[str, str]:
        """The (policy, stack) PlanCache key coordinates of this rung."""
        return (self.policy, self.stack)


def _rung_name(impl: str, stack: str, policy: str) -> str:
    name = impl + ("+stacks" if stack == "auto" else "")
    if policy == "mixed":
        name += "-mixed"
    return name


def degradation_ladder(impl: str, policy: str,
                       stack: str = "auto") -> List[Rung]:
    """The guarded server's fallback chain, most capable first:

      pallas+stacks → pallas stacks-off → mixed→uniform dtype → xla
      decomposed (uniform, stacks-off)

    Built FROM the server's configured operating point by relaxing one
    lever per rung — stack fusion, then the mixed-dtype storage, then the
    fused Pallas engine itself — so a server already running a lower rung
    gets only the rungs at or below it (a uniform/xla server has a one-rung
    ladder) and equivalent variants dedupe.  The terminal rung is always
    decomposed XLA at the uniform dtype: the engine every differential test
    in the repo treats as ground truth."""
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r}")
    if policy not in ("uniform", "mixed"):
        raise ValueError(f"unknown dtype policy {policy!r}")
    if stack not in ("auto", "off"):
        raise ValueError(f"unknown stack policy {stack!r}")
    coords = [
        (impl, stack, policy),            # configured operating point
        (impl, "off", policy),            # stack fusion off
        (impl, "off", "uniform"),         # mixed -> uniform dtype
        ("xla", "off", "uniform"),        # decomposed ground truth
    ]
    rungs: List[Rung] = []
    for i, s, p in coords:
        if all((i, s, p) != (r.impl, r.stack, r.policy) for r in rungs):
            rungs.append(Rung(_rung_name(i, s, p), i, s, p))
    return rungs


# ---------------------------------------------------------------------------
# incident accounting
# ---------------------------------------------------------------------------

# the incident taxonomy (DESIGN.md §14); report_lines() prints these in a
# stable order so CI logs diff cleanly
INCIDENT_KINDS = ("kernel_fault", "nonfinite", "quarantine", "requeue",
                  "corrupt_state", "straggler", "degraded")


@dataclass
class IncidentLog:
    """Counts every resilience event over a server's lifetime.  ``record``
    takes one of ``INCIDENT_KINDS`` (unknown kinds are rejected loudly —
    a typo must not silently open a new taxonomy bucket)."""
    counts: Dict[str, int] = field(default_factory=dict)

    def record(self, kind: str, detail: str = "", n: int = 1) -> None:
        if kind not in INCIDENT_KINDS:
            raise ValueError(f"unknown incident kind {kind!r} "
                             f"(taxonomy: {INCIDENT_KINDS})")
        self.counts[kind] = self.counts.get(kind, 0) + n
        log.warning("incident %s%s", kind, f": {detail}" if detail else "")

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def summary(self) -> str:
        if not self.counts:
            return "incidents=0"
        parts = [f"{k}:{self.counts[k]}" for k in INCIDENT_KINDS
                 if k in self.counts]
        return f"incidents={self.total} ({','.join(parts)})"


# ---------------------------------------------------------------------------
# crash-safe JSON persistence (checksum + fsync + quarantine-aside)
# ---------------------------------------------------------------------------

def payload_checksum(obj: Dict[str, Any]) -> str:
    """sha256 over the canonical (sorted-key) JSON of ``obj`` minus the
    checksum field itself."""
    payload = {k: v for k, v in obj.items() if k != CHECKSUM_FIELD}
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def with_checksum(obj: Dict[str, Any]) -> Dict[str, Any]:
    return {**obj, CHECKSUM_FIELD: payload_checksum(obj)}


class CorruptStateError(ValueError):
    """A persisted state file failed schema or checksum validation."""


def verify_checksum(obj: Dict[str, Any], path: str = "<mem>") -> None:
    """Raises ``CorruptStateError`` on mismatch.  Files written before the
    checksum era (no field) pass — their integrity is vouched for only by
    JSON well-formedness, exactly as before."""
    stored = obj.get(CHECKSUM_FIELD)
    if stored is None:
        return
    actual = payload_checksum(obj)
    if stored != actual:
        raise CorruptStateError(
            f"{path}: payload checksum mismatch "
            f"(stored {stored[:12]}…, actual {actual[:12]}…)")


def atomic_json_dump(obj: Dict[str, Any], path: str, *,
                     checksum: bool = True, indent: int = 1) -> str:
    """Write ``obj`` to ``path`` crash-safely: checksum stamped into the
    payload, contents fsynced BEFORE the atomic rename (a crash between
    write and replace leaves the previous generation intact; a crash after
    replace leaves the new one — never a torn file)."""
    if checksum:
        obj = with_checksum(obj)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=indent)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    # fsync the directory so the rename itself survives a power cut
    dfd = os.open(os.path.dirname(os.path.abspath(path)) or ".", os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)
    return path


def quarantine_file(path: str) -> str:
    """Rename an unreadable state file aside as ``<path>.corrupt`` (never
    clobbering an earlier quarantined generation: ``.corrupt.1``, ...) so
    the caller can rebuild while the evidence survives for post-mortem."""
    dst = f"{path}.corrupt"
    n = 0
    while os.path.exists(dst):
        n += 1
        dst = f"{path}.corrupt.{n}"
    os.replace(path, dst)
    return dst


def load_json_guarded(path: str,
                      validate: Optional[Callable[[Dict[str, Any]], None]]
                      = None,
                      on_corrupt: Optional[Callable[[str, Exception], None]]
                      = None) -> Optional[Dict[str, Any]]:
    """Load a persisted JSON state file, or recover from its corruption.

    Returns the parsed object on success.  On ANY validation failure —
    unreadable bytes, truncated/garbage JSON, checksum mismatch, or a
    ``validate(obj)`` callback raising — the file is renamed aside via
    ``quarantine_file`` and None is returned: the caller rebuilds (replan /
    recalibrate) instead of crashing.  Missing files also return None
    (nothing to quarantine)."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            obj = json.load(f)
        if not isinstance(obj, dict):
            raise CorruptStateError(f"{path}: top level is not an object")
        verify_checksum(obj, path)
        if validate is not None:
            validate(obj)
        return obj
    except (json.JSONDecodeError, UnicodeDecodeError, OSError, ValueError,
            KeyError, TypeError) as e:
        dst = quarantine_file(path)
        log.warning("corrupt state file %s (%s) — renamed aside to %s; "
                    "rebuilding", path, e, dst)
        if on_corrupt is not None:
            on_corrupt(dst, e)
        return None
