"""Accelerator-native engine: the kernel-window arithmetic on JAX/XLA.

One program lives here, jitted end-to-end: :func:`run_fleet_scan`, the
whole-run engine behind Monte-Carlo sweeps (``repro.api.sweep``).  The
iteration/churn loop — kernel windows, parallelism topology, thermal RC +
DVFS governor, cooling churn — runs inside one ``jax.lax.scan``, so a
1000-node fleet steps T iterations (plus the NodeSim-style 30-iteration
thermal warmup) in a single device program, and a sweep vmaps that program
over samples.  Per-kernel noise and TP jitter are drawn from JAX PRNG
streams inside the scan (numpy Generator streams cannot be replayed there),
so this path is *statistically* equivalent to ClusterSim, not
trace-identical; the static thermal lottery (per-device ``r_th`` /
``m_coef``) is passed in as arrays and reproduces ClusterSim's numpy draws
exactly (see :func:`build_fleet_arrays`).  Each iteration's kernel windows
(:func:`_group_summary`) are the vector engine's arithmetic: fed the same
noise draws, they give its ``t_iter`` and ``util`` to float associativity
(tests/test_jax_engine.py).

Everything computes in float64 (``jax.enable_x64(True)`` is entered around
tracing and execution; the global JAX config is left untouched so the
float32 Pallas training substrate is unaffected) with one exception: the
lognormal noise factors (per-kernel, per-collective and TP jitter) evaluate
the inverse error function and the ``exp`` after it in float32
(:func:`_normal_f32`).  A TPU has no native float64; emulated, that
``erf_inv`` over every (sample, node, device, kernel) element was the
largest cost of a sweep, and the factors need no more than float32.  Their
uniforms are still the float64 threefry draws of
``jax.random.normal(key, shape, float64)``, so the stream is the same and
only the rounding differs: ≤ ~3e-7 of max(1, |z|) on the CPU, ≤ ~2.4e-5 on
a TPU v5e, whose float32 ``log`` and ``exp`` are looser.  The same program
runs on the CPU backend (tests, CI) and on a TPU, where float64 is
emulated.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["WindowPlan", "window_plan", "FleetScanSpec", "fleet_scan_spec",
           "build_fleet_arrays", "run_fleet_scan"]


# --------------------------------------------------------------------------- #
# static window plan
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class WindowPlan:
    """The static control flow of one iteration, precomputed per workload.

    The batched/vector engines discover at runtime which compute kernels
    each collective window touches; under a global barrier per collective
    that structure is *static*: after window ``j``'s arrival phase every
    lane has passed kernel ``max(cprod[:j+1])``, and no lane can pass the
    first kernel gated on a comm ``>= j`` before window ``j`` ends.  Those
    bounds give, per window, a closed kernel range for the full-rate
    arrival advance and the slowed window advance — so the whole iteration
    unrolls into ~``Kc + sum(window spans)`` masked vector steps with no
    data-dependent loops, which is what XLA wants.

    Within those ranges every comm gate is provably open and non-binding
    (lane clocks are pulled to each window's global end, which is ≥ every
    previously-ended gate), so the unrolled steps need no gate arithmetic
    at all; gate graphs that *could* deadlock are rejected while building
    the plan — the same error the numpy engines raise at runtime, caught
    statically here.

    Hashable (all-tuple) so compiled programs cache on it via
    ``functools.lru_cache``.
    """

    n_comp: int                               # Kc
    n_comm: int                               # Km
    cprod: Tuple[int, ...]                    # (Km,) producer kernel or -1
    k_wait: Tuple[int, ...]                   # (Kc,) gating comm or -1
    arrival: Tuple[Tuple[int, int], ...]      # (Km,) [lo, hi) full-rate range
    window: Tuple[Tuple[int, int], ...]       # (Km,) [lo, hi) slowed range
    drain_lo: int                             # first kernel of the drain

    @property
    def n_steps(self) -> int:
        """Total unrolled kernel-steps (compile-size indicator)."""
        spans = sum(hi - lo for lo, hi in self.arrival)
        spans += sum(hi - lo for lo, hi in self.window)
        return spans + (self.n_comp - self.drain_lo)


def window_plan(wl) -> WindowPlan:
    """Build (and cache on the workload) the static window plan."""
    cached = getattr(wl, "_c3_jax_plan", None)
    if cached is not None:
        return cached
    from repro.core.c3sim import workload_arrays
    A = workload_arrays(wl)
    k_wait = tuple(int(x) for x in A["wait"])
    cprod = tuple(int(x) for x in A["cprod"])
    Kc, Km = len(k_wait), len(cprod)
    # first kernel gated on comm >= j, per window j
    first_gated = []
    for j in range(Km):
        idx = [i for i, w in enumerate(k_wait) if w >= j]
        first_gated.append(min(idx) if idx else Kc)
    arrival: List[Tuple[int, int]] = []
    window: List[Tuple[int, int]] = []
    maxprod = -1
    for j in range(Km):
        prod = cprod[j]
        if prod >= 0:
            lo = maxprod + 1
            for i in range(lo, prod + 1):
                if k_wait[i] >= j:
                    raise RuntimeError(
                        f"C3Sim[jax]: deadlock — kernel {i} (producer path "
                        f"of comm {j}) is gated on comm {k_wait[i]}, which "
                        f"cannot have ended")
            arrival.append((lo, prod + 1))
            maxprod = max(maxprod, prod)
        else:
            arrival.append((0, 0))
        window.append((maxprod + 1, max(maxprod + 1, first_gated[j])))
    plan = WindowPlan(n_comp=Kc, n_comm=Km, cprod=cprod, k_wait=k_wait,
                      arrival=tuple(arrival), window=tuple(window),
                      drain_lo=maxprod + 1)
    wl._c3_jax_plan = plan
    return plan


# --------------------------------------------------------------------------- #
# one iteration for one barrier group (G lanes) — a scan over a step table
# --------------------------------------------------------------------------- #
# step kinds in the static table
_K_KERNEL = 0       # advance kernel idx (capped→slowed toward prev_end)
_K_COMM = 1         # resolve comm idx: arrival, global end, new barrier
_K_PULL = 2         # pull every lane's clock to the barrier (window end)


@functools.lru_cache(maxsize=64)
def _step_table(plan: WindowPlan):
    """Flatten the window plan into (kind, idx, capped) per scan step.

    Two identities make one uniform kernel-step possible (both follow from
    ``WindowPlan``'s invariant that lane clocks start each window at the
    previous barrier):

      * the arrival-phase ``need`` mask is redundant — for kernels
        ``i <= prod`` a lane has ``ci == i`` iff it still needs to produce,
        so the plain cursor match is the mask;
      * the arrival value is ``max(comp_end[prod], prev_end)`` elementwise
        — lanes that finished the producer in an earlier window did so at
        or before the previous barrier, lanes that finished it this window
        did so at or after it.
    """
    kinds: List[int] = []
    idx: List[int] = []
    capped: List[bool] = []

    def emit(kind, i, c=False):
        kinds.append(kind)
        idx.append(i)
        capped.append(c)

    for j in range(plan.n_comm):
        lo, hi = plan.arrival[j]
        for i in range(lo, hi):
            emit(_K_KERNEL, i)
        emit(_K_COMM, j)
        lo, hi = plan.window[j]
        for i in range(lo, hi):
            emit(_K_KERNEL, i, c=True)
        emit(_K_PULL, 0)
    for i in range(plan.drain_lo, plan.n_comp):
        emit(_K_KERNEL, i)
    return (np.asarray(kinds, np.int32), np.asarray(idx, np.int32),
            np.asarray(capped))


def _iteration_scan(plan: WindowPlan, kappa_comp, kappa_mem,
                    rate_f, rm, work_f, work_b, dur_comm):
    """Run the step-table scan for one barrier group of G lanes.

    Pure function of the per-lane compute rates (G,), the group memory
    rate (scalar), the noised work tables (G, Kc) and collective durations
    (Km,).  Mirrors the vector engine's piecewise-rate integration at the
    same window boundaries, expressed as a `jax.lax.scan` over the
    workload's static `_step_table` so compile time is independent of
    kernel count; see `WindowPlan` for why no gate checks appear here.

    The scan carries only (G,) lane state and emits nothing: that is
    enough for ``t_iter``/``util``.  Two identities keep the carry small:
    at a comm-resolve step every lane's clock *is* its arrival (producers
    just finished at ``t``, everyone else sits at the barrier), and
    completion bookkeeping only needs the in-flight kernel's start time
    (``cur_start``).

    Returns the final carry ``(t, ci, started, gfr, gbr, busy, cur_start,
    prev_end)``.
    """
    G = work_f.shape[0]
    Kc, Km = plan.n_comp, plan.n_comm
    rate_f_s = rate_f / (1.0 + kappa_comp)
    rm_s = rm / (1.0 + kappa_mem)
    w_f = jnp.transpose(work_f)                  # (Kc, G): per-step row reads
    w_b = jnp.transpose(work_b)
    kinds_np, idx_np, capped_np = _step_table(plan)
    xs = (jnp.asarray(kinds_np), jnp.asarray(idx_np),
          jnp.asarray(capped_np))
    dur = dur_comm if Km else jnp.zeros((1,))
    INF = jnp.inf

    def body(carry, x):
        kind, i, cap = x
        t, ci, started, gfr, gbr, busy, cur_start, prev_end = carry
        is_k = kind == _K_KERNEL
        is_c = kind == _K_COMM
        # -- kernel step: full-rate to completion (target mode) or slowed
        #    toward the barrier with partial progress (window mode)
        ts = jnp.where(cap, prev_end, INF)
        rf = jnp.where(cap, rate_f_s, rate_f)
        rmm = jnp.where(cap, rm_s, rm)
        m = is_k & (ci == i)
        ns = m & ~started
        cur_start = jnp.where(ns, t, cur_start)
        gfr = jnp.where(ns, w_f[jnp.minimum(i, Kc - 1)], gfr)
        gbr = jnp.where(ns, w_b[jnp.minimum(i, Kc - 1)], gbr)
        started = started | ns
        dt = gfr / rf + gbr / rmm
        fits = m & (t + dt <= ts)
        t = jnp.where(fits, t + dt, t)
        busy = busy + jnp.where(fits, t - cur_start, 0.0)
        started = started & ~fits
        ci = jnp.where(fits, i + 1, ci)
        avail = ts - t
        pp = m & ~fits & (avail > 0)
        use = jnp.minimum(avail, gfr / rate_f_s)
        gfr_new = jnp.where(pp, gfr - use * rate_f_s, gfr)
        gbr = jnp.where(pp, jnp.maximum(0.0, gbr - (avail - use) * rm_s),
                        gbr)
        # -- comm resolve: the collective globally ends at max arrival
        #    (= max lane clock) + duration, which is the next barrier
        ge = jnp.max(t) + dur[jnp.minimum(i, max(Km, 1) - 1)]
        prev_end = jnp.where(is_c, ge, prev_end)
        # -- barrier pull: window over, every lane ends at the barrier
        t = jnp.where(kind == _K_PULL, prev_end, t)
        new = (t, ci, started, gfr_new, gbr, busy, cur_start, prev_end)
        return new, None

    init = (jnp.zeros((G,)), jnp.zeros((G,), jnp.int32),
            jnp.zeros((G,), bool), jnp.zeros((G,)), jnp.zeros((G,)),
            jnp.zeros((G,)), jnp.zeros((G,)), jnp.asarray(0.0))
    carry, _ = jax.lax.scan(body, init, xs)
    return carry


def _group_summary(plan: WindowPlan, kappa_comp, kappa_mem,
                   rate_f, rm, work_f, work_b, dur_comm):
    """Carry-only iteration for one group: just ``(t_iter, util)``.

    ``t_iter`` is the max lane clock after the drain (per-lane completion
    times are nondecreasing, so the final clock is the lane's last
    completion) held to the final barrier; ``util`` is busy time over it.
    What `run_fleet_scan` iterates.
    """
    carry = _iteration_scan(plan, kappa_comp, kappa_mem, rate_f, rm,
                            work_f, work_b, dur_comm)
    t, busy, prev_end = carry[0], carry[5], carry[7]
    t_iter = jnp.maximum(jnp.max(t), prev_end)
    util = busy / jnp.maximum(t_iter, 1e-12)
    return t_iter, util


# --------------------------------------------------------------------------- #
# whole-run fleet scan: iterations × thermal × churn × topology in one jit
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class FleetScanSpec:
    """The static half of a fleet-scan program (hashable → compile cache).

    Everything that changes array shapes or control flow lives here;
    everything numeric rides in the `build_fleet_arrays` dict, so one
    compiled program serves a whole Monte-Carlo sweep via ``vmap``.
    """

    plan: WindowPlan
    n_nodes: int
    n_devices: int
    iterations: int
    warmup: int = 30                    # NodeSim's thermal warm-up length
    topology: str = "dp"                # dp | pp | tp
    microbatches: int = 8               # pp
    tp_syncs: int = 16                  # tp
    spike: bool = False                 # comm latency spikes enabled


_NODE_FIELDS = ("f_max", "f_min", "p_idle", "peak_gflops", "hbm_gbps",
                "t_amb", "t_throttle", "throttle_slope", "t_ref",
                "leak_quad", "intensity", "tau")


def fleet_scan_spec(workload, sim_cfg, cluster_cfg, iterations: int,
                    devices_per_node: int = 8) -> FleetScanSpec:
    """The static companion of `build_fleet_arrays` for one scenario."""
    from repro.core.topology import make_topology
    cc = cluster_cfg
    if cc.topology not in ("dp", "pp", "tp"):
        raise ValueError(f"unsupported scan topology {cc.topology!r}")
    topo = make_topology(cc, cc.n_nodes, workload, 1.0, seed=0)
    return FleetScanSpec(
        plan=window_plan(workload), n_nodes=cc.n_nodes,
        n_devices=devices_per_node, iterations=int(iterations),
        topology=cc.topology, microbatches=cc.microbatches,
        tp_syncs=int(getattr(topo, "K", 1)),
        spike=bool(sim_cfg.comm_spike_p > 0))


def build_fleet_arrays(workload, preset, sim_cfg, cluster_cfg,
                       caps_w: Optional[float], seed: int,
                       devices_per_node: int = 8,
                       rng_seed: int = 0) -> Dict[str, np.ndarray]:
    """The numeric half of a fleet scan: per-lane thermal lottery, per-node
    preset constants, churn event tables, topology constants, PRNG key.

    The thermal draws (``r_th`` spread + straggler slot, silicon-lottery
    ``m_coef``) reproduce ``ThermalModel``'s numpy streams exactly — node
    ``n`` draws from ``default_rng(seed + 7919 * n)`` with the same
    clip/boost arithmetic, via an actual `ThermalModel` instance — so a
    scan run shares ClusterSim's static physics; only the per-iteration
    noise streams differ (JAX PRNG keyed on ``rng_seed``).

    To batch runs for a sweep, build one dict per sample and stack every
    entry along a new leading axis before calling `run_fleet_scan`.
    """
    from repro.core.c3sim import workload_arrays
    from repro.core.thermal import PRESETS, ThermalModel
    from repro.core.topology import make_topology

    cc = cluster_cfg
    N, G = cc.n_nodes, devices_per_node
    if cc.node_presets is not None:
        if len(cc.node_presets) != N:
            raise ValueError(f"node_presets has {len(cc.node_presets)} "
                             f"entries for {N} nodes")
        presets = [PRESETS[p] if isinstance(p, str) else p
                   for p in cc.node_presets]
    else:
        presets = [preset] * N

    arrays: Dict[str, np.ndarray] = {}
    r_th = np.empty((N, G))
    m_coef = np.empty((N, G))
    per_node = {f: np.empty(N) for f in _NODE_FIELDS}
    churn = cc.churn or {}
    max_ev = max([len(cm.events) for cm in churn.values()] + [1])
    drift_rate = np.zeros(N)
    ev_t = np.full((N, max_ev), np.inf)
    ev_dev = np.zeros((N, max_ev), np.int32)
    ev_factor = np.ones((N, max_ev))
    for n in range(N):
        boost = (cc.straggler_boost if n == cc.straggler_node
                 else cc.healthy_boost)
        tm = ThermalModel(presets[n], G, seed=seed + 7919 * n,
                          straggler_boost=boost, churn=None)
        r_th[n] = tm.r_th
        m_coef[n] = tm.m_coef
        for f in _NODE_FIELDS:
            per_node[f][n] = getattr(presets[n], f)
        cm = churn.get(n)
        if cm is not None:
            drift_rate[n] = cm.drift_rate
            for e, ev in enumerate(cm.events):
                ev_t[n, e] = ev.t
                ev_dev[n, e] = ev.device
                ev_factor[n, e] = ev.factor
    arrays["r_th"] = r_th
    arrays["m_coef"] = m_coef
    arrays.update(per_node)
    arrays["drift_rate"] = drift_rate
    arrays["ev_t"] = ev_t
    arrays["ev_dev"] = ev_dev
    arrays["ev_factor"] = ev_factor
    tdp = np.array([p.tdp for p in presets])
    arrays["tdp_caps"] = np.repeat(tdp[:, None], G, axis=1)
    arrays["caps"] = (np.full((N, G), float(caps_w))
                      if caps_w is not None else arrays["tdp_caps"].copy())

    A = workload_arrays(workload)
    arrays["gflop"] = A["gflop"]
    arrays["gbyte"] = A["gbyte"]
    arrays["cbytes"] = A["cbytes"]

    grad = cc.grad_bytes
    if grad is None:
        grad = sum(c.bytes for c in workload.comm
                   if c.name.startswith("rs_"))
        if grad <= 0:
            grad = workload.total_bytes / 3.0
    topo = make_topology(cc, N, workload, float(grad), seed=seed)
    arrays["comm_const"] = np.asarray(topo.comm_time(), float)
    arrays["tp_jitter"] = np.asarray(getattr(topo, "jitter", 0.0), float)
    arrays["tp_skew_cost"] = np.asarray(
        getattr(topo, "skew_cost", 0.0), float)
    for f in ("kappa_comp", "kappa_mem", "gemm_eff", "comm_gbps", "noise",
              "comm_spike_p", "comm_spike_mult"):
        arrays[f] = np.asarray(getattr(sim_cfg, f), float)
    arrays["key"] = np.asarray(
        np.random.default_rng(rng_seed).integers(0, 2 ** 32, size=2),
        np.uint32)
    return arrays


# erf_inv(x) = p(w)·x with w = -log(1 - x²), per branch of w: Giles'
# single-precision polynomials (XLA's float32 ErfInv) in w - 2.5 below 5
# and in sqrt(w) - 3 below 16, and past 16, where float32 inputs never
# reach but 1 - |u| of a float64 uniform does (down to 2^-53, w ≤ 36.05),
# a fit of erf_inv(x)/x in sqrt(w) - 5 (relative error 4.3e-9 against
# mpmath on sqrt(w) in [4, 6.01]).  Rows: Horner order, highest first.
_ERFINV_F32 = np.array([
    (2.81022636e-08, 3.43273939e-07, -3.5233877e-06, -4.39150654e-06,
     0.00021858087, -0.00125372503, -0.00417768164, 0.246640727,
     1.50140941),
    (-0.000200214257, 0.000100950558, 0.00134934322, -0.00367342844,
     0.00573950773, -0.0076224613, 0.00943887047, 1.00167406, 2.83297682),
    (0.0, 0.0, 5.28401461e-06, -2.16353146e-05, 7.53193890e-05,
     -0.000214010739, -0.000138525720, 1.01030028, 4.84990645),
], np.float32)


def _sqrt2_erf_inv_f32(u):
    """``√2·erf_inv(u)`` in float32 for float64 ``u`` in (-1, 1).

    ``t = 1 - |u|`` is formed in float64 before the cast, so a ``u`` within
    2^-25 of ±1 (which would round to ±1 in float32, and give ±inf) keeps
    its distance from the pole; ``1 - u² = t·(2 - t)``.  Odd in ``u``.
    """
    f32 = np.float32
    t = (1.0 - jnp.abs(u)).astype(jnp.float32)
    w = -jnp.log(t * (f32(2.0) - t))
    centre, tail = w < f32(5.0), w < f32(16.0)
    x = jnp.where(centre, w - f32(2.5),
                  jnp.sqrt(w) - jnp.where(tail, f32(3.0), f32(5.0)))
    coef = [jnp.where(centre, c_centre, jnp.where(tail, c_tail, c_far))
            for c_centre, c_tail, c_far in _ERFINV_F32.T]
    p = coef[0]
    for c in coef[1:]:
        p = c + p * x
    return f32(np.sqrt(2.0)) * p * u.astype(jnp.float32)


def _normal_f32(key, shape):
    """The draw of ``jax.random.normal(key, shape, float64)`` in float32.

    Same key, same bits, same float64 uniform on [nextafter(-1, 0), 1) as
    ``jax.random.normal``; only the transform to a normal runs in float32
    (see `_sqrt2_erf_inv_f32`).  Matches the float64 draw to ~3e-7 of
    max(1, |z|) where float32 ``log`` is correctly rounded (the CPU), to
    ~2.4e-5 on a TPU v5e, and is finite for every uniform the draw can
    give.
    """
    u = jax.random.uniform(key, shape, jnp.float64,
                           np.nextafter(-1.0, 0.0), 1.0)
    return _sqrt2_erf_inv_f32(u)


def _fleet_scan_core(spec: FleetScanSpec, a: Dict):
    """The pure scan program: warmup (uncoupled, TDP caps) then the main
    coupled loop, all under one trace.  ``a`` is the `build_fleet_arrays`
    dict as jnp arrays."""
    plan = spec.plan
    N, G = spec.n_nodes, spec.n_devices
    Kc, Km = plan.n_comp, plan.n_comm
    base_key = a["key"]

    def iteration(rate_f, rm, work_f, work_b, dur_comm):
        fn = jax.vmap(lambda rf, r, wf, wb, dc: _group_summary(
            plan, a["kappa_comp"], a["kappa_mem"], rf, r, wf, wb, dc))
        return fn(rate_f, rm, work_f, work_b, dur_comm)

    def m_eff(temp):
        dt = jnp.maximum(temp - a["t_ref"][:, None], 0.0)
        return a["m_coef"] * (1.0 + a["leak_quad"][:, None] * dt * dt)

    def effective_r_th(t_sim):
        drift = 1.0 + a["drift_rate"][:, None] * t_sim[:, None] / 3600.0
        active = jnp.where(t_sim[:, None] >= a["ev_t"], a["ev_factor"], 1.0)
        onehot = a["ev_dev"][:, :, None] == jnp.arange(G)[None, None, :]
        ev = jnp.prod(jnp.where(onehot, active[:, :, None], 1.0), axis=1)
        return a["r_th"] * drift * ev

    def lognormal(key, shape, sigma):
        z = _normal_f32(key, shape)
        return jnp.exp(sigma.astype(jnp.float32) * z).astype(jnp.float64)

    def draw_noise(key):
        k1, k2, k3 = jax.random.split(key, 3)
        noise_c = lognormal(k1, (N, G, Kc), a["noise"])
        base = a["cbytes"][None, :] / (a["comm_gbps"] * 1e9)
        dur = base * lognormal(k2, (N, Km), a["noise"])
        if spec.spike:
            ks, ku = jax.random.split(k3)
            hit = jax.random.uniform(ks, (N, Km)) < a["comm_spike_p"]
            mult = a["comm_spike_mult"] * (
                1.0 + jax.random.uniform(ku, (N, Km)))
            dur = dur * jnp.where(hit, mult, 1.0)
        return noise_c, dur

    def run_iteration(freq, key):
        noise_c, dur_comm = draw_noise(key)
        rate_f = (a["peak_gflops"][:, None] * a["gemm_eff"]
                  * freq / a["f_max"][:, None])
        work_f = a["gflop"][None, None, :] * noise_c
        work_b = a["gbyte"][None, None, :] * noise_c
        t_local, util = iteration(rate_f, a["hbm_gbps"], work_f, work_b,
                                  dur_comm)
        return t_local, util

    def topology_step(t_local, key):
        if spec.topology == "dp":
            t_fleet = jnp.max(t_local) + a["comm_const"]
            lead = jnp.max(t_local) - t_local
        elif spec.topology == "pp":
            tau = t_local / spec.microbatches
            t_fleet = (jnp.sum(tau)
                       + (spec.microbatches - 1) * jnp.max(tau)
                       + a["comm_const"])
            lead = t_fleet - t_local
        else:                           # tp
            K = spec.tp_syncs
            w = lognormal(key, (N, K), a["tp_jitter"])
            w = w / jnp.sum(w, axis=1, keepdims=True)
            seg = t_local[:, None] * w
            seg_max = jnp.max(seg, axis=0)
            t_skew = (a["tp_skew_cost"]
                      * jnp.sum(seg_max - jnp.min(seg, axis=0))
                      if N > 1 else 0.0)
            t_fleet = jnp.sum(seg_max) + t_skew + a["comm_const"]
            lead = jnp.sum(seg_max[None, :] - seg, axis=1)
        return t_fleet, lead

    def commit(temp, freq, cap, t_sim, util, t_interval):
        """`ThermalModel.update`, vectorized over (N, G) lanes: power from
        current freq/util, RC thermal step, then the governor picks
        next-interval frequencies from the *new* temperature."""
        u_pow = 0.8 + 0.2 * jnp.clip(util, 0.0, 1.0)
        draw = a["p_idle"][:, None] + m_eff(temp) * freq * u_pow
        power = jnp.minimum(draw, cap)
        t_ss = a["t_amb"][:, None] + effective_r_th(t_sim) * power
        alpha = 1.0 - jnp.exp(-t_interval[:, None] / a["tau"][:, None])
        temp = temp + alpha * (t_ss - temp)
        budget = jnp.maximum(cap - a["p_idle"][:, None], 1.0)
        f_cap = budget / (m_eff(temp) * a["intensity"][:, None])
        over = jnp.maximum(temp - a["t_throttle"][:, None], 0.0)
        f_hard = a["f_max"][:, None] * (
            1.0 - a["throttle_slope"][:, None] * over)
        freq = jnp.clip(jnp.minimum(f_cap, f_hard),
                        a["f_min"][:, None], a["f_max"][:, None])
        return temp, freq, power, t_sim + t_interval

    temp0 = a["t_amb"][:, None] + 20.0 + jnp.zeros((N, G))
    freq0 = a["f_max"][:, None] + jnp.zeros((N, G))

    def warm_body(carry, i):
        temp, freq, t_sim = carry
        k = jax.random.fold_in(base_key, i)
        t_local, util = run_iteration(freq, k)
        temp, freq, _, t_sim = commit(temp, freq, a["tdp_caps"], t_sim,
                                      util, t_local)
        return (temp, freq, t_sim), None

    (temp, freq, _), _ = jax.lax.scan(
        warm_body, (temp0, freq0, jnp.zeros(N)), jnp.arange(spec.warmup))
    t_sim = jnp.zeros(N)                # churn clock resets post-warmup

    def main_body(carry, i):
        temp, freq, t_sim = carry
        k = jax.random.fold_in(base_key, spec.warmup + 1 + i)
        kt = jax.random.fold_in(base_key, 2 ** 20 + i)  # tp jitter stream
        t_local, util = run_iteration(freq, k)
        t_fleet, lead = topology_step(t_local, kt)
        if spec.topology == "tp":       # active wait: hot inside collectives
            util_eff = (util * t_local[:, None]
                        + (t_fleet - t_local)[:, None]) / t_fleet
        else:                           # barrier/bubble wait: idle and cool
            util_eff = util * (t_local / t_fleet)[:, None]
        temp, freq, power, t_sim = commit(temp, freq, a["caps"], t_sim,
                                          util_eff, jnp.full(N, t_fleet))
        node_power = jnp.sum(power, axis=1)
        out = (t_fleet, jnp.max(lead), jnp.argmax(t_local),
               jnp.argmin(lead), jnp.sum(node_power))
        return (temp, freq, t_sim), out

    (temp, freq, _), series = jax.lax.scan(
        main_body, (temp, freq, t_sim), jnp.arange(spec.iterations))
    t_fleet, lead_max, slowest, strag, power = series
    return {"t_fleet": t_fleet, "lead_max": lead_max,
            "slowest_node": slowest, "straggler_node": strag,
            "fleet_power": power, "temp": temp, "freq": freq}


@functools.lru_cache(maxsize=16)
def _compiled_scan(spec: FleetScanSpec, batched: bool):
    core = functools.partial(_fleet_scan_core, spec)
    return jax.jit(jax.vmap(core) if batched else core)


def run_fleet_scan(spec: FleetScanSpec,
                   arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Execute one fleet run — or, when every array carries a leading
    sample axis, a whole batch of runs — as a single jitted scan program.

    Returns per-iteration fleet series — ``t_fleet``, ``lead_max``,
    ``slowest_node`` (argmax of node iteration time), ``straggler_node``
    (argmin of lead) and ``fleet_power`` — and the final thermal
    ``temp``/``freq`` state, as numpy arrays.

    Three profiler spans name its host stages: ``fleet_scan.put`` (inputs
    to the device), ``fleet_scan.call`` (dispatch, and compile on a cache
    miss) and ``fleet_scan.fetch`` (wait for the device, outputs to the
    host).
    """
    batched = arrays["r_th"].ndim == 3
    fn = _compiled_scan(spec, batched)
    with jax.enable_x64(True):
        with jax.profiler.TraceAnnotation("fleet_scan.put"):
            args = {k: jnp.asarray(v) for k, v in arrays.items()}
        with jax.profiler.TraceAnnotation("fleet_scan.call"):
            out = fn(args)
    with jax.profiler.TraceAnnotation("fleet_scan.fetch"):
        return {k: np.asarray(v) for k, v in out.items()}
