"""Monte-Carlo and cartesian fleet sweeps over scenario distributions.

A :class:`SweepSpec` turns one registered fleet scenario into a *population*
of runs: distributions over scalar knobs (``fleet.straggler_boost``, sim
noise, …), per-node device-preset draws, and per-sample thermal-lottery
seeds (fresh ``r_th`` spreads — the silicon lottery variability studies
sample over).  Sampling is Monte-Carlo (``samples`` draws from ``seed``) or
cartesian (``grid`` axes, same dotted-path format as the CLI ``--grid``).

Execution compiles the whole population into as few device programs as
possible: every sample whose *static* shape (fleet size, topology, workload
plan, iteration count) matches runs inside one batched
:func:`repro.core.jax_engine.run_fleet_scan` — a single ``vmap``-ed XLA
program over the sample axis.  Populations whose shapes differ fall back
to per-sample ``ClusterSim`` stepping (same physics, numpy speed).  Both
paths drop any closed-loop manager: sweeps measure the *open-loop* fleet
dynamics, so the distribution reflects thermal imbalance rather than the
mitigation policy.

The result is a versioned JSON artifact (``format: lit-silicon-sweep``,
schema documented in docs/sweeps.md): per-sample fleet metrics — tail-mean
``t_fleet``, throughput, worst node lead, fleet power, and ``recovery``
(throughput relative to a healthy reference fleet with every boost and
churn multiplier at 1.0) — plus summary quantiles over the population.

Reproducibility contract (tested in tests/test_scenario_api.py):

  * the same `SweepSpec` always produces the same sample overrides, the
    same thermal lotteries, and the same per-iteration noise keys;
  * `SweepSpec` round-trips through JSON exactly (the scenario codec's
    ``{"$float": ...}`` discipline for NaN/±Inf);
  * sample ``k`` of an N-sample sweep equals sample ``k`` of an M-sample
    sweep for ``k < min(N, M)`` — draws are keyed per sample, not shared.
"""
from __future__ import annotations

import dataclasses
import json
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.spec import (Scenario, _decode_value, _encode,
                            with_overrides)

SWEEP_SPEC_FORMAT = "lit-silicon-sweep-spec"
SWEEP_FORMAT = "lit-silicon-sweep"
SWEEP_VERSION = 1

__all__ = ["Dist", "SweepSpec", "run_sweep", "summarize",
           "SWEEP_FORMAT", "SWEEP_SPEC_FORMAT", "SWEEP_VERSION"]


# --------------------------------------------------------------------------- #
# sampling spec
# --------------------------------------------------------------------------- #
@dataclass
class Dist:
    """One scalar sampling distribution for a dotted scenario path.

    ``kind``: ``"uniform"`` (low/high), ``"loguniform"`` (low/high > 0),
    ``"normal"`` (mean/std), or ``"choice"`` (uniform over ``choices``,
    which may hold any JSON value — preset names, bools, …).
    """

    kind: str = "uniform"
    low: float = 0.0
    high: float = 1.0
    mean: float = 0.0
    std: float = 1.0
    choices: Optional[List[Any]] = None

    def validate(self, path: str) -> None:
        """Check kind-specific invariants; ``path`` labels the error."""
        if self.kind not in ("uniform", "loguniform", "normal", "choice"):
            raise ValueError(f"{path}: unknown Dist kind {self.kind!r}")
        if self.kind == "choice" and not self.choices:
            raise ValueError(f"{path}: kind='choice' needs choices")
        if self.kind == "loguniform" and self.low <= 0:
            raise ValueError(f"{path}: loguniform needs low > 0")

    def sample(self, rng: np.random.Generator) -> Any:
        """One draw from the distribution using ``rng``."""
        if self.kind == "uniform":
            return float(rng.uniform(self.low, self.high))
        if self.kind == "loguniform":
            return float(math.exp(rng.uniform(math.log(self.low),
                                              math.log(self.high))))
        if self.kind == "normal":
            return float(self.mean + self.std * rng.standard_normal())
        return self.choices[int(rng.integers(len(self.choices)))]


@dataclass
class SweepSpec:
    """A population of runs over one registered fleet scenario.

    Monte-Carlo mode (``grid`` unset): ``samples`` draws, each sampling
    every entry of ``dists`` (dotted scenario path → `Dist`), optionally
    redrawing per-node presets iid from ``node_preset_pool``, and — when
    ``vary_thermal_seed`` — taking a fresh thermal-lottery seed
    (``scenario seed + sample index``) so each sample is a different
    silicon/cooling draw.  Cartesian mode (``grid`` set): one run per cell
    of the axes' cartesian product; ``samples``/``dists`` are ignored.

    ``seed`` drives the override sampling *and* the per-sample iteration
    noise keys; two sweeps with the same spec are identical populations.
    """

    scenario: str = ""
    samples: int = 16
    seed: int = 0
    iterations: Optional[int] = None        # None: the scenario's own count
    dists: Dict[str, Dist] = field(default_factory=dict)
    node_preset_pool: Optional[List[str]] = None
    vary_thermal_seed: bool = True
    grid: Optional[Dict[str, List[Any]]] = None

    # -------------------------------------------------------------- checks
    def validate(self) -> "SweepSpec":
        """Check the spec is runnable (scenario named, sane counts, every
        Dist valid); returns self so it chains."""
        if not self.scenario:
            raise ValueError("SweepSpec.scenario must name a registered "
                             "scenario")
        if self.grid is None and self.samples < 1:
            raise ValueError("SweepSpec.samples must be >= 1")
        for path, dist in self.dists.items():
            dist.validate(f"dists[{path!r}]")
        return self

    # -------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        """JSON-safe nested dict (same NaN/Inf escaping as `Scenario`)."""
        return _encode(self)

    def to_json(self, indent: Optional[int] = 2) -> str:
        """Versioned sweep-spec document: ``{format, version, sweep}``."""
        return json.dumps({"format": SWEEP_SPEC_FORMAT,
                           "version": SWEEP_VERSION,
                           "sweep": self.to_dict()},
                          indent=indent, sort_keys=True, allow_nan=False)

    @classmethod
    def from_dict(cls, d: dict) -> "SweepSpec":
        """Inverse of `to_dict`; unknown keys rejected at both the spec
        and the per-Dist level, result validated."""
        if not isinstance(d, dict):
            raise ValueError("sweep: expected an object")
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - names)
        if unknown:
            raise ValueError(f"sweep: unknown key(s) {unknown} "
                             f"(known: {sorted(names)})")
        kw = {k: _decode_value(v, f"sweep.{k}") for k, v in d.items()
              if k != "dists"}
        dists = {}
        for path, dd in (d.get("dists") or {}).items():
            if not isinstance(dd, dict):
                raise ValueError(f"sweep.dists[{path!r}]: expected an "
                                 "object")
            dnames = {f.name for f in dataclasses.fields(Dist)}
            unknown = sorted(set(dd) - dnames)
            if unknown:
                raise ValueError(f"sweep.dists[{path!r}]: unknown key(s) "
                                 f"{unknown}")
            dists[path] = Dist(**{k: _decode_value(v,
                                                   f"sweep.dists[{path}].{k}")
                                  for k, v in dd.items()})
        kw["dists"] = dists
        return cls(**kw).validate()

    @classmethod
    def from_json(cls, text: str) -> "SweepSpec":
        """Parse a sweep-spec document, checking format/version."""
        data = json.loads(text)
        if not isinstance(data, dict) or data.get("format") != SWEEP_SPEC_FORMAT:
            raise ValueError(f"not a {SWEEP_SPEC_FORMAT} document")
        if int(data.get("version", 0)) > SWEEP_VERSION:
            raise ValueError(f"sweep-spec version {data['version']} is "
                             f"newer than supported {SWEEP_VERSION}")
        if "sweep" not in data:
            raise ValueError("sweep-spec document carries no 'sweep' body")
        return cls.from_dict(data["sweep"])

    def save(self, path: str) -> None:
        """Write the `to_json` document to ``path``."""
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path: str) -> "SweepSpec":
        """Read a sweep-spec document from ``path``."""
        with open(path) as f:
            return cls.from_json(f.read())


# --------------------------------------------------------------------------- #
# sample materialization
# --------------------------------------------------------------------------- #
_HEALTHY = {"fleet.straggler_boost": 1.0, "fleet.healthy_boost": 1.0,
            "fleet.churn": None}


def _sample_overrides(spec: SweepSpec, base: Scenario
                      ) -> List[Tuple[str, Dict[str, Any], int]]:
    """(label, overrides, thermal_seed) per sample, deterministically.

    Each sample gets its own child generator (seeded ``(spec.seed, k)``) so
    the population is prefix-stable: growing ``samples`` never changes
    earlier draws.
    """
    out = []
    if spec.grid is not None:
        combos: List[List[Tuple[str, Any]]] = [[]]
        for key, values in spec.grid.items():
            combos = [c + [(key, v)] for c in combos for v in values]
        for combo in combos:
            label = ",".join(f"{k}={_fmt(v)}" for k, v in combo)
            out.append((label, dict(combo), base.seed))
        return out
    n_nodes = base.fleet.n_nodes if base.fleet is not None else 0
    for k in range(spec.samples):
        rng = np.random.default_rng([spec.seed, k])
        ov: Dict[str, Any] = {}
        for path in sorted(spec.dists):
            ov[path] = spec.dists[path].sample(rng)
        if spec.node_preset_pool:
            pool = spec.node_preset_pool
            ov["fleet.node_presets"] = [
                pool[int(i)] for i in rng.integers(len(pool), size=n_nodes)]
        seed = base.seed + k if spec.vary_thermal_seed else base.seed
        label = f"sample={k}" + "".join(
            f",{p}={_fmt(v)}" for p, v in sorted(ov.items()))
        out.append((label, ov, seed))
    return out


def _fmt(v: Any) -> str:
    return f"{v:.4g}" if isinstance(v, float) else str(v)


# --------------------------------------------------------------------------- #
# execution
# --------------------------------------------------------------------------- #
def _tail(x: np.ndarray, n: int = 30) -> np.ndarray:
    return x[-min(n, len(x)):]


def _series_metrics(t_fleet: np.ndarray, lead_max: np.ndarray,
                    power: np.ndarray) -> Dict[str, float]:
    return {
        "t_fleet_s": float(np.mean(_tail(t_fleet))),
        "throughput": float(np.mean(1.0 / _tail(t_fleet))),
        "lead_max_s": float(np.mean(_tail(lead_max))),
        "fleet_power_w": float(np.mean(_tail(power))),
    }


def _run_batch_jax(variants: List[Scenario],
                   seeds: List[int], noise_seeds: List[int],
                   iterations: int) -> Optional[List[Dict[str, float]]]:
    """All samples whose static shape matches, as one vmapped scan program;
    None when shapes diverge (caller falls back to per-sample runs)."""
    from repro.core.jax_engine import (build_fleet_arrays, fleet_scan_spec,
                                       run_fleet_scan)
    with TraceAnnotation("sweep.workload"):
        workloads, specs = [], []
        for i, sc in enumerate(variants):
            # rows whose workload specs agree share one built workload:
            # building is pure, and one workload held per row until the
            # arrays stage grows the heap the garbage collector walks
            if i and sc.workload == variants[i - 1].workload:
                wl = workloads[-1]
            else:
                wl = sc.workload.build()
            if sc.fleet.topology not in ("dp", "pp", "tp"):
                return None
            workloads.append(wl)
            specs.append(fleet_scan_spec(wl, sc.sim, sc.fleet, iterations,
                                         collect="summary",
                                         devices_per_node=sc.node.devices))
        if len(set(specs)) != 1:
            return None                   # mixed shapes: no single program
    with TraceAnnotation("sweep.fleet_arrays"):
        arrays = [build_fleet_arrays(
            wl, sc.node.build_preset(), sc.sim, sc.fleet, sc.node.caps_w,
            seed, devices_per_node=sc.node.devices, rng_seed=nseed)
            for sc, wl, seed, nseed in zip(variants, workloads, seeds,
                                           noise_seeds)]
        stacked = {k: np.stack([a[k] for a in arrays]) for k in arrays[0]}
    out = run_fleet_scan(specs[0], stacked)
    with TraceAnnotation("sweep.collect"):
        return [_series_metrics(out["t_fleet"][i], out["lead_max"][i],
                                out["fleet_power"][i])
                for i in range(len(variants))]


def _run_one_python(sc: Scenario, seed: int,
                    iterations: int) -> Dict[str, float]:
    """Per-sample fallback: plain ClusterSim stepping (numpy engines)."""
    from repro.api.runner import build_scenario
    built = build_scenario(sc.replace(seed=seed), iterations=iterations)
    for _ in range(iterations):
        built.cluster.step()
    h = built.cluster.history
    return _series_metrics(
        np.array([x["t_fleet"] for x in h]),
        np.array([np.max(x["lead"]) for x in h]),
        np.array([x["power"] for x in h]))


def summarize(values: Dict[str, List[float]]) -> Dict[str, Dict[str, float]]:
    """Mean + p10/p50/p90 per metric over the sample population."""
    out = {}
    for name, xs in values.items():
        arr = np.asarray(xs, float)
        out[name] = {
            "mean": float(np.mean(arr)),
            "p10": float(np.percentile(arr, 10)),
            "p50": float(np.percentile(arr, 50)),
            "p90": float(np.percentile(arr, 90)),
        }
    return out


def run_sweep(spec: SweepSpec) -> dict:
    """Execute the sweep and return the artifact dict (see docs/sweeps.md).

    Raises ``ValueError`` for non-fleet scenarios — sweeps are fleet
    populations by definition (node-level studies sweep via the CLI
    ``--grid`` rows instead).

    Under a profiler session the call is one ``run_sweep`` span holding a
    span per stage of its host work (docs/sweeps.md, "Tracing a sweep").
    """
    with TraceAnnotation("run_sweep"):
        with TraceAnnotation("sweep.sample"):
            from repro.api.registry import get_scenario
            spec.validate()
            base = get_scenario(spec.scenario)
            if base.fleet is None:
                raise ValueError(f"sweep requires a fleet scenario; "
                                 f"{spec.scenario!r} is node-scoped")
            base = base.replace(manager=None)       # open-loop population
            iters = (base.iterations if spec.iterations is None
                     else int(spec.iterations))
            mode = "grid" if spec.grid is not None else "mc"

            cells = _sample_overrides(spec, base)
            # the healthy reference rides the same batch as its final row
            variants = [with_overrides(base, ov) for _, ov, _ in cells]
            variants.append(with_overrides(base, dict(_HEALTHY)))
            seeds = [s for _, _, s in cells] + [base.seed]
            noise_seeds = [spec.seed * 1_000_003 + k
                           for k in range(len(cells))]
            # the reference's noise stream sits far past any realistic
            # sample index
            noise_seeds.append(spec.seed * 1_000_003 + 999_999_937)

        rows = _run_batch_jax(variants, seeds, noise_seeds, iters)
        engine = "jax-scan"
        if rows is None:
            engine = "python"
            rows = [_run_one_python(sc, seed, iters)
                    for sc, seed in zip(variants, seeds)]

        with TraceAnnotation("sweep.collect"):
            ref = rows.pop()
            ref_tput = max(ref["throughput"], 1e-12)
            samples = []
            for (label, ov, seed), row in zip(cells, rows):
                samples.append({
                    "sample": len(samples), "label": label,
                    "overrides": _encode(ov), "thermal_seed": seed,
                    **row, "recovery": row["throughput"] / ref_tput,
                })
            names = ("t_fleet_s", "throughput", "lead_max_s",
                     "fleet_power_w", "recovery")
            summary = summarize({n: [s[n] for s in samples] for n in names})
            return {
                "format": SWEEP_FORMAT, "version": SWEEP_VERSION,
                "scenario": spec.scenario, "mode": mode, "engine": engine,
                "seed": spec.seed, "iterations": iters,
                "n_samples": len(samples),
                "sweep_spec": spec.to_dict(),
                "reference": ref,
                "samples": samples,
                "summary": summary,
            }
