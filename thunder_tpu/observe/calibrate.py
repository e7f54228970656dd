"""Cost-model calibration: fit the modeled constants from measured time.

``core/cost_model.py``'s verdicts rest on hand-modeled v5e constants
(efficiencies, launch overheads, ICI bandwidth). The measured-time residual
ledger (``observe/profile.py``) records, per decision, what those constants
predicted and what a profiled window measured — this module closes the loop:

- **Fit** (:func:`fit`): per-family closed-form least squares over the
  ledger's fit components. Each cost function is affine in the reciprocal
  efficiency and the launch overhead —
  ``measured = stream_us/eff + launch`` (adamw),
  ``measured - boundary_us = flop_us/eff + launch`` (sub-blocks),
  ``measured = launch + recv_bytes/bw·1e6`` (collectives) —
  so two accumulated records per family already determine both constants;
  more records over-determine and the normal equations average the noise.
- **Persist** (:func:`save` / :func:`configure`): fitted constants land in
  schema-versioned ``cost_calibration.json`` under the directory an explicit
  ``configure()`` or ``THUNDER_TPU_CALIBRATION_DIR`` names (same atomic
  tmp+replace write as the kernel-quarantine set; never the compile-cache
  directory by default — an overlay that changes verdicts must not travel
  with a cache reused across commits), keyed by platform — a v5e fit never
  leaks onto v5p.
- **Apply**: :func:`configure`/:func:`activate` install the CURRENT
  platform's constants into ``cost_model``'s overlay, so every later cost
  dict is stamped ``"calibration": <platform>`` and every affected verdict
  records a typed ``calibrated[...]`` reason — calibration changes
  decisions loudly, never silently.
- **Gate** (:func:`check_budget` + the committed ``CALIBRATION_BUDGETS.json``):
  expected per-platform ranges for each fitted constant; a fit outside its
  band is a loud test failure (an XLA/platform upgrade that shifts measured
  reality must surface as drift, not silently recalibrate verdicts).
"""

from __future__ import annotations

import json
import os
import threading
import time

from thunder_tpu.core import cost_model as _cost_model
from thunder_tpu.observe import registry as _observe

_FILENAME = "cost_calibration.json"
SCHEMA_VERSION = 1

# fit sanity clamps: a degenerate window (two near-identical records, a
# noisy CPU timer) must not install a nonsensical overlay
_EFFICIENCY_BOUNDS = (1e-3, 1e3)   # CPU-interpret "efficiency" vs the TPU
                                   # roofline legitimately lands far from 1
_LAUNCH_BOUNDS_US = (0.0, 1e7)
_BANDWIDTH_BOUNDS = (1e3, 1e13)    # bytes/s


def platform() -> str:
    """The calibration platform key for this process: ``tpu-<chip>`` from
    the device table (``core.devices.chip_spec`` — a device_kind the table
    lacks raises), or ``cpu-interpret`` for every CPU host."""
    import jax

    from thunder_tpu.core.devices import chip_spec

    backend = jax.default_backend()
    if backend == "cpu":
        return "cpu-interpret"
    if backend != "tpu":
        return backend
    return "tpu-" + chip_spec().name


# ---------------------------------------------------------------------------
# per-family least-squares fits
# ---------------------------------------------------------------------------

def _lstsq2(xs, ys):
    """Least-squares (a, b) for y = a·x + b via the 2x2 normal equations.
    Returns ``None`` on a degenerate design (all x equal — slope and
    intercept cannot be separated)."""
    n = len(xs)
    sx = sum(xs)
    sy = sum(ys)
    sxx = sum(x * x for x in xs)
    sxy = sum(x * y for x, y in zip(xs, ys))
    det = n * sxx - sx * sx
    if abs(det) < 1e-12 * max(sxx, 1.0):
        return None
    a = (n * sxy - sx * sy) / det
    b = (sy * sxx - sx * sxy) / det
    return a, b


def _clamp(v, lo, hi):
    return min(max(v, lo), hi)


def _fit_slope_intercept(records, x_key, y_of, *, fallback_intercept):
    """Fit measured = slope·x + intercept over one family's records.
    Single-record (or degenerate-design) fallback: pin the intercept at the
    current modeled constant and solve the slope from the mean point."""
    pts = [(r[x_key], y_of(r)) for r in records
           if r.get(x_key) and r.get("measured_us") is not None]
    pts = [(x, y) for x, y in pts if x > 0]
    if not pts:
        return None
    xs = [p[0] for p in pts]
    ys = [p[1] for p in pts]
    sol = _lstsq2(xs, ys) if len(pts) >= 2 else None
    if sol is None:
        slope = max(sum(ys) / len(ys) - fallback_intercept, 0.0) \
            / (sum(xs) / len(xs))
        return slope, fallback_intercept, len(pts)
    slope, intercept = sol
    return slope, intercept, len(pts)


def fit(records, platform_key: str | None = None) -> dict:
    """Fit calibrated constants from residual-ledger records (the
    ``measured`` ones — ``unattributed`` records carry no clock). Returns::

        {"platform", "fitted_from", "constants": {NAME: value, ...},
         "families": {"adamw": n, "subblock": n, "comm": n}}

    Families with no measured records simply contribute no constants — a
    partial fit is a valid overlay (unfitted names keep their modeled
    defaults through ``cost_model.constant``)."""
    if platform_key is None:
        platform_key = platform()
    measured = [r for r in records if r.get("status") == "measured"
                and r.get("measured_us") is not None]
    constants: dict = {}
    families: dict = {}

    # adamw: measured = stream_us·(1/eff) + launch
    adamw = [r for r in measured if r.get("kind") == "fusion"
             and r.get("stream_us")]
    sol = _fit_slope_intercept(
        adamw, "stream_us", lambda r: r["measured_us"],
        fallback_intercept=_cost_model.constant("ADAMW_LAUNCH_OVERHEAD_US"))
    if sol:
        slope, intercept, n = sol
        families["adamw"] = n
        if slope > 0:
            constants["ADAMW_FUSED_EFFICIENCY"] = _clamp(
                1.0 / slope, *_EFFICIENCY_BOUNDS)
        constants["ADAMW_LAUNCH_OVERHEAD_US"] = _clamp(
            intercept, *_LAUNCH_BOUNDS_US)

    # sub-blocks: measured - boundary_us = flop_us·(1/eff) + launch
    # (mlp/attn/decode-layer share the SUBBLOCK_* constants)
    sub = [r for r in measured if r.get("kind") == "block"
           and r.get("flop_us")]
    sol = _fit_slope_intercept(
        sub, "flop_us",
        lambda r: r["measured_us"] - (r.get("boundary_us") or 0.0),
        fallback_intercept=_cost_model.constant("SUBBLOCK_LAUNCH_OVERHEAD_US"))
    if sol:
        slope, intercept, n = sol
        families["subblock"] = n
        if slope > 0:
            constants["SUBBLOCK_FUSED_EFFICIENCY"] = _clamp(
                1.0 / slope, *_EFFICIENCY_BOUNDS)
        constants["SUBBLOCK_LAUNCH_OVERHEAD_US"] = _clamp(
            intercept, *_LAUNCH_BOUNDS_US)

    # collectives: measured = launch + recv_bytes/bw · 1e6
    comm = [r for r in measured if r.get("kind") == "comm"
            and r.get("recv_bytes")]
    sol = _fit_slope_intercept(
        comm, "recv_bytes", lambda r: r["measured_us"],
        fallback_intercept=_cost_model.constant("COLLECTIVE_LAUNCH_US"))
    if sol:
        slope, intercept, n = sol
        families["comm"] = n
        if slope > 0:
            constants["ICI_BW_BYTES_PER_S"] = _clamp(
                1e6 / slope, *_BANDWIDTH_BOUNDS)
        constants["COLLECTIVE_LAUNCH_US"] = _clamp(
            intercept, *_LAUNCH_BOUNDS_US)

    result = {"platform": platform_key,
              "fitted_from": len(measured),
              "constants": {k: round(float(v), 6)
                            for k, v in constants.items()},
              "families": families}
    _observe.set_gauge("calib.constants_fitted", len(constants))
    _observe.set_gauge("calib.records_fitted_from", len(measured))
    _observe.event("calibration_fit", platform=platform_key,
                   fitted_from=len(measured), **result["constants"])
    return result


# ---------------------------------------------------------------------------
# persistence (the quarantine pattern: attach + atomic write + env bootstrap)
# ---------------------------------------------------------------------------

class CalibrationStore:
    """Per-platform fitted constants, persisted as schema-versioned JSON:
    ``{"version": 1, "platforms": {plat: {"constants": {...},
    "fitted_from": n, "time": ...}}}``."""

    def __init__(self, path: str | None = None):
        self._lock = threading.Lock()
        self._platforms: dict[str, dict] = {}
        self._path: str | None = None
        if path is not None:
            self.attach(path)

    # -- persistence --------------------------------------------------------
    def attach(self, path: str) -> None:
        """Bind to ``path``: merge what a previous process fitted there
        (disk wins for platforms this process has not fitted), persist the
        union."""
        path = os.path.abspath(path)
        with self._lock:
            self._path = path
            for plat, rec in self._load(path).items():
                self._platforms.setdefault(plat, rec)
            self._persist()
        _observe.set_gauge("calib.platforms_persisted", len(self._platforms))

    @staticmethod
    def _load(path: str) -> dict:
        try:
            with open(path) as f:
                data = json.load(f)
            if data.get("version") != SCHEMA_VERSION:
                return {}  # schema drift: refit rather than misread
            plats = data.get("platforms", {})
            return plats if isinstance(plats, dict) else {}
        except Exception:
            return {}  # missing or torn file: start empty, rewrite on save

    def _persist(self) -> None:
        if self._path is None:
            return
        tmp = self._path + ".tmp"
        os.makedirs(os.path.dirname(self._path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"version": SCHEMA_VERSION,
                       "platforms": self._platforms}, f, indent=2)
        os.replace(tmp, self._path)

    # -- mutation / queries -------------------------------------------------
    def save(self, fit_result: dict) -> None:
        plat = fit_result["platform"]
        with self._lock:
            self._platforms[plat] = {
                "constants": dict(fit_result["constants"]),
                "fitted_from": fit_result.get("fitted_from", 0),
                "time": time.time()}
            self._persist()
        _observe.set_gauge("calib.platforms_persisted", len(self._platforms))
        _observe.event("calibration_saved", platform=plat,
                       constants=len(fit_result["constants"]))

    def constants_for(self, plat: str) -> dict | None:
        rec = self._platforms.get(plat)
        return None if rec is None else dict(rec.get("constants", {}))

    def platforms(self) -> tuple[str, ...]:
        return tuple(self._platforms)

    @property
    def path(self) -> str | None:
        return self._path


_store = CalibrationStore()


def store() -> CalibrationStore:
    return _store


def activate(plat: str | None = None) -> bool:
    """Install the store's constants for ``plat`` (default: this process's
    platform) into ``cost_model``'s overlay. Returns whether an overlay was
    installed — ``False`` leaves the modeled defaults untouched."""
    if plat is None:
        plat = platform()
    constants = _store.constants_for(plat)
    if not constants:
        return False
    known = {k: v for k, v in constants.items()
             if k in _cost_model.CALIBRATABLE}
    if not known:
        return False
    _cost_model.apply_calibration(plat, known)
    _observe.set_gauge("calib.active_constants", len(known))
    _observe.event("calibration_activated", platform=plat,
                   constants=len(known))
    return True


def configure(directory: str) -> bool:
    """Persist calibrations under ``directory``, then activate the current
    platform's constants if any were ever fitted."""
    _store.attach(os.path.join(str(directory), _FILENAME))
    if not _store.platforms():
        return False  # nothing ever fitted: don't touch the jax backend
    return activate()


def save(fit_result: dict, *, apply: bool = True) -> None:
    """Persist a :func:`fit` result; by default also activate it when it
    matches this process's platform."""
    _store.save(fit_result)
    if apply and fit_result["platform"] == platform():
        activate(fit_result["platform"])


def reset(path: str | None = None) -> CalibrationStore:
    """Replace the process store with a fresh instance and drop the
    cost-model overlay (test harness: simulates a process restart; pass
    ``path`` to re-read a persisted store — then call :func:`activate`)."""
    global _store
    _cost_model.clear_calibration()
    _store = CalibrationStore(path)
    return _store


# ---------------------------------------------------------------------------
# budget gate (the CENSUS_BUDGETS.json pattern)
# ---------------------------------------------------------------------------

def check_budget(fit_result: dict, budget: dict) -> list:
    """Check one platform's fitted constants against the committed bands
    (``CALIBRATION_BUDGETS.json``: ``{platform: {NAME: [lo, hi], ...}}``
    entries, pre-selected for the fit's platform). Returns violation
    strings — empty means within budget. A fitted constant with no band is
    a violation too: new fit families must be budgeted when they land."""
    violations: list = []
    plat = fit_result.get("platform", "?")
    constants = fit_result.get("constants", {})
    for name, value in sorted(constants.items()):
        band = budget.get(name)
        if band is None:
            violations.append(
                f"{plat}: fitted constant {name}={value:g} has no budget "
                f"band — add one to CALIBRATION_BUDGETS.json")
            continue
        lo, hi = band
        if not (lo <= value <= hi):
            violations.append(
                f"{plat}: {name}={value:g} outside budget [{lo:g}, {hi:g}] "
                f"— measured reality shifted; refit and re-band deliberately")
    _observe.set_gauge("calib.budget_violations", len(violations))
    return violations


if os.environ.get("THUNDER_TPU_CALIBRATION_DIR"):
    configure(os.environ["THUNDER_TPU_CALIBRATION_DIR"])
