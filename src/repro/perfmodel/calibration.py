"""Threshold calibration + predicted-vs-measured cross-validation
(DESIGN.md §13; paper §IV.A-B; DeLTA, Lym et al. 2019).

Two jobs live here:

1. **The paper's (Ct, Nt) thresholds.**  ``calibrate`` reproduces the
   one-time profiling sweep (analytic cost model, or a ``measure(layer,
   layout) -> seconds`` callback timing the real Pallas engines via
   ``pallas_conv_measure``); ``select_conv_layout`` / ``select_pool_layout``
   apply the two-rule decision per layer.  Thresholds persist as rows keyed
   by **(hardware id, storage dtype)**: the element size scales every byte
   term and the sublane width, and the crossover points measured under the
   interpreter on one machine are NOT the crossover points of a real TPU —
   a server must only plan under thresholds swept on its own silicon.
   ``hardware_id()`` is ``jax.devices()[0].device_kind`` plus an
   ``/interpret`` suffix for interpreter-mode timings; legacy files (flat
   {Ct, Nt} or per-dtype ``rows``) load as the unversioned ``default``
   hardware row, and lookups for an unknown hardware id fall back to it.

2. **Prediction-error cross-validation.**  DeLTA's discipline: an analytic
   model you never compare against measurement drifts silently.
   ``cross_validate`` times the real Pallas kernels on the calibration sweep,
   fits the ``CalibratedCostModel`` scale (analytic priors x measured
   overlay), and reports per-point predicted-vs-measured relative error —
   the ``prediction_error`` number the fusion bench emits and
   ``check_trajectory`` gates lower-is-better.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import os
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from repro.configs.paper_table1 import ConvLayer, PoolLayer
from repro.dtypes import DEFAULT_DTYPE, canon_dtype, dtype_bytes, jnp_dtype
from repro.perfmodel.traffic import DEFAULT_DTYPE_BYTES, conv_cost
from repro.runtime.resilience import (atomic_json_dump, load_json_guarded,
                                      quarantine_file)

log = logging.getLogger("repro.perfmodel.calibration")

# Row key for threshold files that predate hardware versioning (and for
# callers that do not say where their measurements came from).  An
# unversioned legacy file IS this row.
DEFAULT_HARDWARE = "default"


def hardware_id(interpret: Optional[bool] = None) -> str:
    """Stable identity of the silicon a measurement ran on.  Interpreter
    timings get their own rows: they measure the Pallas *interpreter* on the
    host CPU, and must never be mistaken for compiled-TPU thresholds.
    ``interpret=None`` follows ``resolve_interpret`` (compiled on a TPU)."""
    import jax
    from repro.kernels import resolve_interpret
    kind = jax.devices()[0].device_kind
    return f"{kind}/interpret" if resolve_interpret(interpret) else kind


# ---------------------------------------------------------------------------
# the paper's two-threshold heuristic + calibration sweep
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Thresholds:
    Ct: int
    Nt: int


def select_conv_layout(l: ConvLayer, th: Thresholds) -> str:
    """Verbatim paper heuristic (§IV.A)."""
    if l.Ci < th.Ct:
        return "CHWN"
    if l.N >= th.Nt:
        return "CHWN"
    return "NCHW"


def select_pool_layout(l: Optional[PoolLayer] = None) -> str:
    """Paper §IV.B: pooling always prefers CHWN (window access in NCHW is
    strided/uncoalesced; on TPU, sub-lane-sized W tiles)."""
    return "CHWN"


def _cal_base() -> ConvLayer:
    return ConvLayer("CAL", 128, 384, 13, 3, 256, 1, "cal")


def calibrate(measure: Optional[Callable[[ConvLayer, str], float]] = None,
              base: Optional[ConvLayer] = None,
              dtype_bytes: int = DEFAULT_DTYPE_BYTES) -> Thresholds:
    """One-time per-hardware calibration (paper Fig. 4).

    Sweeps C with fixed large N (finding Ct = first C where NCHW wins) and
    N with mid-size C (finding Nt = first N where CHWN wins again).  Uses the
    analytical cost model unless a ``measure(layer, layout) -> seconds``
    callback (real-hardware profiling) is supplied.

    ``dtype_bytes`` is the STORAGE element size the thresholds are valid
    for: halving it halves every byte term and doubles the sublane width, so
    each storage dtype gets its own (Ct, Nt) row (a measured ``measure``
    callback must time kernels at the same element size).
    """
    base = base or _cal_base()
    cost = measure or (lambda l, lay: conv_cost(l, lay, dtype_bytes).total_s)

    Ct = 1
    for c in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512):
        l = ConvLayer("CAL", 64, base.Co, base.HW, base.F, c, base.S, "cal")
        if cost(l, "NCHW") < cost(l, "CHWN"):
            Ct = c
            break
    else:
        Ct = 512

    Nt = None
    for n in (16, 32, 64, 128, 256, 512):
        l = ConvLayer("CAL", n, base.Co, base.HW, base.F, max(base.Ci, Ct),
                      base.S, "cal")
        if cost(l, "CHWN") <= cost(l, "NCHW"):
            Nt = n
            break
    if Nt is None:
        Nt = 1 << 30     # CHWN never wins at high C on this hardware
    return Thresholds(Ct=Ct, Nt=Nt)


# ---------------------------------------------------------------------------
# persisted threshold rows: {hardware id: {dtype: {Ct, Nt}}}
# ---------------------------------------------------------------------------

def _parse_table(obj: Dict) -> Dict[str, Dict[str, Dict]]:
    if "hardware" in obj:
        return {hw: {canon_dtype(k): v for k, v in ent.get("rows", {}).items()}
                for hw, ent in obj["hardware"].items()}
    if "rows" in obj:
        return {DEFAULT_HARDWARE:
                {canon_dtype(k): v for k, v in obj["rows"].items()}}
    if "Ct" in obj:                    # legacy single-row file
        return {DEFAULT_HARDWARE:
                {DEFAULT_DTYPE: {"Ct": obj["Ct"], "Nt": obj["Nt"]}}}
    return {}


def _load_table(path: str,
                on_corrupt: Optional[Callable[[str, Exception], None]] = None
                ) -> Dict[str, Dict[str, Dict]]:
    """All persisted rows keyed (hardware id, canonical dtype).  Reads the
    v3 hardware-versioned format ({"hardware": {hw: {"rows": ...}}}), the
    v2 per-dtype format ({"rows": {dtype: {Ct, Nt}}}) and the legacy flat
    {"Ct": ..., "Nt": ...} file — both pre-v3 shapes become the unversioned
    ``DEFAULT_HARDWARE`` row, which is exactly how their measurements were
    taken (no hardware recorded).

    Corrupt files (truncated/garbage JSON, checksum mismatch — §14) are
    renamed aside as ``*.corrupt`` and read as an EMPTY table, so callers
    recalibrate instead of raising: thresholds are a ~4 s measured sweep,
    always cheaper than a server that refuses to start."""
    obj = load_json_guarded(path, on_corrupt=on_corrupt)
    if obj is None:
        return {}
    try:
        return _parse_table(obj)
    except (KeyError, TypeError, AttributeError, ValueError) as e:
        dst = quarantine_file(path)
        log.warning("malformed threshold table %s (%s) — renamed aside to "
                    "%s; recalibrating", path, e, dst)
        if on_corrupt is not None:
            on_corrupt(dst, e)
        return {}


def save_thresholds(th: Thresholds, path: str, *,
                    dtype: str = DEFAULT_DTYPE,
                    source: str = "measured",
                    hardware: Optional[str] = None) -> str:
    """Merge one (hardware, dtype) row into the persisted threshold table.
    ``hardware=None`` writes the unversioned default row (the pre-v3
    behaviour, kept so explicit-threshold callers stay hardware-agnostic).
    The write is crash-safe (§14): payload checksum + fsync before the
    atomic replace."""
    dtype = canon_dtype(dtype)
    hw = hardware or DEFAULT_HARDWARE
    table = _load_table(path) if os.path.exists(path) else {}
    table.setdefault(hw, {})[dtype] = {**dataclasses.asdict(th),
                                       "source": source}
    atomic_json_dump({"version": 3,
                      "hardware": {h: {"rows": rows}
                                   for h, rows in table.items()}}, path)
    return path


def load_thresholds(path: str, dtype: str = DEFAULT_DTYPE,
                    hardware: Optional[str] = None,
                    on_corrupt: Optional[Callable[[str, Exception], None]]
                    = None) -> Thresholds:
    """The persisted row for (``hardware``, ``dtype``); KeyError when no row
    covers it (callers treat that as "calibrate it now").  A corrupt file
    reads as an empty table (renamed aside — §14), so it also lands here as
    KeyError -> recalibrate.

    ``hardware=None`` means "this machine": try the current hardware id
    (interpret, then compiled), then the unversioned default row.  An
    explicit hardware id missing from the file also falls back to the
    default row — an unversioned legacy file serves every hardware until
    per-hardware measurements replace it."""
    table = _load_table(path, on_corrupt=on_corrupt)
    dtype = canon_dtype(dtype)
    if hardware is None:
        cands = [hardware_id(True), hardware_id(False), DEFAULT_HARDWARE]
    else:
        cands = [hardware, DEFAULT_HARDWARE]
    for hw in cands:
        row = table.get(hw, {}).get(dtype)
        if row is not None:
            return Thresholds(Ct=row["Ct"], Nt=row["Nt"])
    raise KeyError(f"no threshold row for dtype={dtype!r} under any of "
                   f"{cands} in {path}")


def pallas_conv_measure(*, proxy_hw: int = 8, proxy_co: int = 32,
                        reps: int = 2, interpret: Optional[bool] = None,
                        dtype: str = DEFAULT_DTYPE
                        ) -> Callable[[ConvLayer, str], float]:
    """Build a ``measure(layer, layout) -> seconds`` callback that times the
    real Pallas conv engines (direct-CHWN / im2col-MM-NCHW).

    N and Ci are taken from the layer verbatim (they are what ``calibrate``
    sweeps); HW and Co are clamped to the proxy size.  Operands are created
    in the storage ``dtype`` so the timing reflects the element size the
    thresholds will be used for.  The 1-byte (int8) row times the engines on
    genuine int8 activations — random values in the quantized range, with
    float weights, exactly what the mixed-dtype executor feeds them (the
    per-channel scale rides the weights).  Each timing is the best of
    ``reps`` after one warm-up call (which also absorbs compile)."""
    import jax
    import jax.numpy as jnp
    from repro.cnn.layers import conv_forward
    dtype = canon_dtype(dtype)
    jdt = jnp_dtype(dtype)

    def measure(l: ConvLayer, layout: str) -> float:
        hw = max(min(l.HW, proxy_hw), l.F)
        co = min(l.Co, proxy_co)
        key = jax.random.PRNGKey(0)
        if layout == "CHWN":
            shape = (l.Ci, hw, hw, l.N)
        else:
            shape = (l.N, l.Ci, hw, hw)
        if dtype == "int8":
            x = jax.random.randint(key, shape, -127, 128, jnp.int8)
            w = (jax.random.normal(key, (co, l.Ci, l.F, l.F), jnp.float32)
                 * 0.1)
        else:
            x = jax.random.normal(key, shape, jnp.float32).astype(jdt)
            w = (jax.random.normal(key, (co, l.Ci, l.F, l.F), jnp.float32)
                 * 0.1).astype(jdt)

        def f():
            return conv_forward(x, w, layout, l.S, 0, impl="pallas",
                                interpret=interpret)

        jax.block_until_ready(f())          # warm-up + compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            best = min(best, time.perf_counter() - t0)
        return best

    return measure


def proxied_layer(l: ConvLayer, *, proxy_hw: int = 8,
                  proxy_co: int = 32) -> ConvLayer:
    """The layer ``pallas_conv_measure`` ACTUALLY times: N and Ci verbatim,
    HW/Co clamped to the proxy.  Analytic predictions that will be compared
    against those measurements must be computed on this layer — predicting
    the full layer while measuring the proxy would bake the proxy ratio into
    every reported error."""
    hw = max(min(l.HW, proxy_hw), l.F)
    co = min(l.Co, proxy_co)
    return dataclasses.replace(l, HW=hw, Co=co)


def measured_thresholds(path: Optional[str] = None, *,
                        dtype: str = DEFAULT_DTYPE, force: bool = False,
                        measure: Optional[Callable[[ConvLayer, str], float]]
                        = None, interpret: Optional[bool] = None,
                        hardware: Optional[str] = None,
                        on_corrupt: Optional[
                            Callable[[str, Exception], None]] = None
                        ) -> Thresholds:
    """Serving-default thresholds for one storage dtype: persisted
    measurement, not the analytic sweep.  Loads ``path``'s row for this
    hardware + ``dtype`` when present (unless ``force``); otherwise runs
    ``calibrate`` at that dtype's element size with the Pallas measurement
    callback and merges the new row in under this machine's hardware id.
    A corrupt threshold file is renamed aside (``on_corrupt`` notified —
    §14) and simply re-measured."""
    dtype = canon_dtype(dtype)
    hw = hardware or hardware_id(interpret)
    if path and os.path.exists(path) and not force:
        try:
            return load_thresholds(path, dtype, hardware=hw,
                                   on_corrupt=on_corrupt)
        except KeyError:
            pass                        # file exists but lacks this row
    th = calibrate(measure or pallas_conv_measure(interpret=interpret,
                                                  dtype=dtype),
                   dtype_bytes=dtype_bytes(dtype))
    if path:
        save_thresholds(th, path, dtype=dtype, source="measured",
                        hardware=hw)
    return th


# ---------------------------------------------------------------------------
# predicted-vs-measured cross-validation (the DeLTA loop)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CalibrationPoint:
    """One sweep point: the proxied layer timed by ``pallas_conv_measure``
    next to what the (calibrated) analytic model predicted for it."""
    Ci: int
    N: int
    layout: str
    analytic_s: float        # raw roofline seconds, no measured overlay
    predicted_s: float       # after the fitted per-layout scale
    measured_s: float
    rel_err: float           # |predicted - measured| / measured

    def to_obj(self) -> Dict:
        return dataclasses.asdict(self)


@dataclass
class CrossValidation:
    """The fitted overlay + its residuals for one (hardware, dtype)."""
    hardware: str
    dtype: str
    scales: Dict[str, Tuple[float, float]]   # layout -> (a, b): t = a * s^b
    points: List[CalibrationPoint]
    mean_rel_err: float
    max_rel_err: float

    def to_obj(self) -> Dict:
        return {"hardware": self.hardware, "dtype": self.dtype,
                "scales": {k: list(v) for k, v in self.scales.items()},
                "mean_rel_err": self.mean_rel_err,
                "max_rel_err": self.max_rel_err,
                "points": [p.to_obj() for p in self.points]}


def _fit_overlay(pairs: List[Tuple[float, float]]) -> Tuple[float, float]:
    """Fit measured ≈ a * analytic^b in log space.

    A pure multiplicative scale (b = 1) is the honest overlay when the
    analytic model already tracks the measurement's shape; under the
    interpreter the per-call dispatch floor compresses the dynamic range, so
    the log-log slope soaks up that compression.  Geometric-mean residuals
    make the fit scale-free (a 2x error on a fast point weighs the same as
    on a slow one)."""
    lp = [math.log(max(p, 1e-12)) for p, _ in pairs]
    lm = [math.log(max(m, 1e-12)) for _, m in pairs]
    n = len(pairs)
    mp, mm = sum(lp) / n, sum(lm) / n
    var = sum((x - mp) ** 2 for x in lp)
    if var < 1e-12:
        return math.exp(mm - mp), 1.0      # all analytic values equal
    b = sum((x - mp) * (y - mm) for x, y in zip(lp, lm)) / var
    a = math.exp(mm - b * mp)
    return a, b


def cross_validate(measure: Optional[Callable[[ConvLayer, str], float]]
                   = None, *, dtype: str = DEFAULT_DTYPE,
                   interpret: Optional[bool] = None,
                   hardware: Optional[str] = None,
                   proxy_hw: int = 8, proxy_co: int = 32,
                   reps: int = 2,
                   c_points: Tuple[int, ...] = (4, 32, 128),
                   n_points: Tuple[int, ...] = (16, 64, 256)
                   ) -> CrossValidation:
    """Time the real Pallas kernels on the calibration sweep and score the
    analytic model's predictions against them (DeLTA's validation loop).

    Per layout, a two-parameter overlay (``_fit_overlay``) maps analytic
    roofline seconds onto the measured clock — that overlay IS what
    ``CalibratedCostModel`` applies — and each point reports the relative
    error of the calibrated prediction.  The analytic side is computed on
    ``proxied_layer`` (the layer the measurement actually ran), so the
    comparison is apples-to-apples.
    """
    dtype = canon_dtype(dtype)
    db = dtype_bytes(dtype)
    hw_id = hardware or hardware_id(interpret)
    measure = measure or pallas_conv_measure(
        proxy_hw=proxy_hw, proxy_co=proxy_co, reps=reps,
        interpret=interpret, dtype=dtype)
    base = _cal_base()
    sweep = ([ConvLayer("CAL", 64, base.Co, base.HW, base.F, c, base.S,
                        "cal") for c in c_points] +
             [ConvLayer("CAL", n, base.Co, base.HW, base.F, base.Ci, base.S,
                        "cal") for n in n_points])
    raw: Dict[str, List[Tuple[ConvLayer, float, float]]] = {}
    for l in sweep:
        proxy = proxied_layer(l, proxy_hw=proxy_hw, proxy_co=proxy_co)
        for lay in ("CHWN", "NCHW"):
            analytic = conv_cost(proxy, lay, db).total_s
            measured = measure(l, lay)
            raw.setdefault(lay, []).append((l, analytic, measured))
    scales: Dict[str, Tuple[float, float]] = {}
    points: List[CalibrationPoint] = []
    for lay, rows in raw.items():
        a, b = _fit_overlay([(an, me) for _, an, me in rows])
        scales[lay] = (a, b)
        for l, an, me in rows:
            pred = a * (an ** b)
            err = abs(pred - me) / max(me, 1e-12)
            points.append(CalibrationPoint(
                Ci=l.Ci, N=l.N, layout=lay, analytic_s=an,
                predicted_s=pred, measured_s=me, rel_err=err))
    errs = [p.rel_err for p in points]
    return CrossValidation(hardware=hw_id, dtype=dtype, scales=scales,
                           points=points,
                           mean_rel_err=sum(errs) / len(errs),
                           max_rel_err=max(errs))
