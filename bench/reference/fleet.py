"""Plain reference of a Monte-Carlo fleet sweep, in numpy.

It follows the published semantics of one data-parallel fleet run, written
down once more from the paper's model and the sweep's documented contract,
and shares no code with the program:

* Workload (paper Fig. 2): one FSDP training iteration of a decoder model.
  Per layer, forward: an all-gather ``ag_f`` and eight compute kernels, the
  first gated on that all-gather; backward in reverse layer order: an
  all-gather ``ag_b``, the eight kernels reversed at twice the FLOPs (the
  first gated on ``ag_b``), then a reduce-scatter ``rs_b`` produced by the
  layer's last kernel; an optimizer kernel gated on the last reduce-scatter.
* Node (C3): each device runs its compute kernels in order, FLOP work at a
  rate proportional to its clock and byte work at HBM rate, FLOP work first.
  Collectives resolve one at a time in order: a device arrives once it has
  finished the collective's producer (or at once), the collective ends when
  the last device of the node has arrived plus its duration, and between a
  device's arrival and that end its compute runs slower by ``1 + kappa``.
* Fleet (DP): the iteration takes the slowest node's time plus a ring
  all-reduce of the gradients; every node waits idle at the barrier.
* Thermal: first-order RC per device, leakage quadratic in temperature, a
  power-cap governor with a hard throttle; 30 uncoupled warm-up iterations
  at TDP caps, then the coupled iterations at the configured caps.
* Sweep contract: sample ``k`` takes thermal seed ``seed + k`` and noise
  seed ``sweep_seed * 1_000_003 + k``; the healthy reference row takes the
  scenario seed, boosts of 1.0 and noise seed
  ``sweep_seed * 1_000_003 + 999_999_937``.  Device ``g`` of node ``n``
  draws its thermal lottery from ``default_rng(thermal_seed + 7919 * n)``;
  per-iteration noise comes from JAX's threefry stream keyed by
  ``default_rng(noise_seed).integers(0, 2**32, 2)``.

``precision="bfloat16"`` rounds every stored value to bfloat16: the lower
precision that the control runs in.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

FWD = ("attn_n", "qkv_ip", "attn_fa", "attn_op", "mlp_n", "mlp_gp",
       "mlp_up", "mlp_dp")
TAIL = 30                       # iterations the per-run means are taken over


def workload_table(wl: dict) -> dict:
    """Kernel table of one FSDP iteration from the model's shapes."""
    d, dff = wl["hidden_size"], wl["intermediate_size"]
    hd = wl["head_dim"]
    qd, kvd = wl["num_attention_heads"] * hd, wl["num_key_value_heads"] * hd
    L, B, S = wl["num_hidden_layers"], wl["batch"], wl["seq"]
    shards, nb = wl["n_shards"], wl["dtype_bytes"]
    T = B * S
    layer_bytes = (d * qd + 2 * d * kvd + qd * d + 3 * d * dff) * nb
    ag = layer_bytes * (shards - 1) / shards
    gflop = {"attn_n": 0.0, "qkv_ip": 2 * T * d * (qd + 2 * kvd) / 1e9,
             "attn_fa": 2 * 2 * T * S * d / 2 / 1e9,
             "attn_op": 2 * T * qd * d / 1e9, "mlp_n": 0.0,
             "mlp_gp": 2 * T * d * dff / 1e9, "mlp_up": 2 * T * d * dff / 1e9,
             "mlp_dp": 2 * T * dff * d / 1e9}
    vec = T * d * nb * 4 / 1e9
    kf, kb, wait = [], [], []        # per compute kernel
    cbytes, cprod = [], []           # per collective
    for _ in range(L):
        cbytes.append(ag)
        cprod.append(-1)
        for i, name in enumerate(FWD):
            kf.append(gflop[name])
            kb.append(vec if name.endswith("_n") else 0.0)
            wait.append(len(cbytes) - 1 if i == 0 else -1)
    for _ in range(L):
        cbytes.append(ag)
        cprod.append(-1)
        for i, name in enumerate(reversed(FWD)):
            kf.append(2 * gflop[name])
            kb.append(2 * vec if name.endswith("_n") else 0.0)
            wait.append(len(cbytes) - 1 if i == 0 else -1)
        cbytes.append(ag)
        cprod.append(len(kf) - 1)
    kf.append(0.0)
    kb.append(3 * layer_bytes * L / shards / 1e9)
    wait.append(len(cbytes) - 1)
    return {"gflop": np.array(kf), "gbyte": np.array(kb),
            "wait": np.array(wait), "cbytes": np.array(cbytes),
            "cprod": np.array(cprod), "grad_bytes": L * ag}


def thermal_lottery(preset: dict, n_nodes: int, G: int, seed: int,
                    boosts: Sequence[float]):
    """(r_th, m_coef), each (N, G), from each node's own generator."""
    r_th = np.empty((n_nodes, G))
    m_coef = np.empty((n_nodes, G))
    for n in range(n_nodes):
        rng = np.random.default_rng(seed + 7919 * n)
        spread = np.clip(rng.normal(0.0, preset["r_th_spread"] / 2, G),
                         -preset["r_th_spread"], preset["r_th_spread"])
        r = preset["r_th_mean"] * (1.0 + spread)
        r[int(rng.integers(G))] *= boosts[n]
        r_th[n] = r
        m_coef[n] = (0.81 * (preset["tdp"] - preset["p_idle"])
                     / preset["f_max"]
                     * (1.0 + rng.normal(0.0, preset["m_spread"], G)))
    return r_th, m_coef


def noise_key(noise_seed: int) -> np.ndarray:
    return np.asarray(np.random.default_rng(noise_seed).integers(
        0, 2 ** 32, size=2), np.uint32)


class NoiseStream:
    """Per-iteration kernel and collective noise of several rows, drawn
    from JAX's threefry stream on the default device."""

    def __init__(self, keys: np.ndarray, n_nodes: int, G: int, Kc: int,
                 Km: int, sigma: float):
        import jax
        import jax.numpy as jnp
        self.keys = keys

        def draw(key, i):
            k1, k2, _ = jax.random.split(jax.random.fold_in(key, i), 3)
            return (jnp.exp(sigma * jax.random.normal(k1, (n_nodes, G, Kc))),
                    jnp.exp(sigma * jax.random.normal(k2, (n_nodes, Km))))

        self._draw = jax.jit(jax.vmap(draw, in_axes=(0, None)))

    def __call__(self, i: int):
        import jax
        with jax.enable_x64(True):
            c, m = self._draw(self.keys, i)
            return np.asarray(c, np.float64), np.asarray(m, np.float64)


class FleetReference:
    """Rows of one fleet configuration, each a whole run, side by side."""

    def __init__(self, config: dict, precision: str = "float64"):
        self.cfg = config
        self.tab = workload_table(config)
        if precision == "float64":
            self.q = lambda x: x
        elif precision == "bfloat16":
            import ml_dtypes
            self.q = lambda x: np.asarray(x).astype(
                ml_dtypes.bfloat16).astype(np.float64)
        else:
            raise ValueError(f"unknown precision {precision!r}")

    # ---------------------------------------------------------- one iteration
    def iteration(self, freq, noise_c, dur):
        """Local iteration time (R, N) and compute utilisation (R, N, G)."""
        q, tab, sim, pre = self.q, self.tab, self.cfg["sim"], self.cfg["preset"]
        gflop, gbyte, wait = tab["gflop"], tab["gbyte"], tab["wait"]
        cprod = tab["cprod"]
        Kc, Km = len(gflop), len(cprod)
        rf = q(pre["peak_gflops"] * sim["gemm_eff"] * freq / pre["f_max"])
        rm = pre["hbm_gbps"]
        rf_s = q(rf / (1.0 + sim["kappa_comp"]))
        rm_s = rm / (1.0 + sim["kappa_mem"])
        # kernel-major (Kc, R, N, G), so one kernel's work is contiguous
        work_f = np.ascontiguousarray(np.moveaxis(q(gflop * noise_c), -1, 0))
        work_b = np.ascontiguousarray(np.moveaxis(q(gbyte * noise_c), -1, 0))
        shape = freq.shape
        t = np.zeros(shape)
        ci = np.zeros(shape, np.int64)
        started = np.zeros(shape, bool)
        rem_f = np.zeros(shape)
        rem_b = np.zeros(shape)
        start = np.zeros(shape)
        busy = np.zeros(shape)

        def begin(m, k: int):
            """Lanes ``m``, all at kernel ``k``, load it if not yet started."""
            nonlocal rem_f, rem_b, start, started
            new = m & ~started
            rem_f = np.where(new, work_f[k], rem_f)
            rem_b = np.where(new, work_b[k], rem_b)
            start = np.where(new, t, start)
            started = started | new

        def finish(m, t_new):
            nonlocal t, busy, ci, started
            t = np.where(m, q(t_new), t)
            busy = np.where(m, q(busy + t - start), busy)
            ci = np.where(m, ci + 1, ci)
            started = started & ~m

        def run_full(until: int):
            """Full rate until every lane has finished kernel ``until``."""
            for k in range(int(ci.min()), until + 1):
                m = ci == k
                if not m.any():
                    continue
                begin(m, k)
                finish(m, t + rem_f / rf + rem_b / rm)

        e_prev = np.zeros(shape[:-1])
        for j in range(Km):
            if cprod[j] >= 0:
                gated = wait[int(ci.min()):cprod[j] + 1]
                if (gated >= j).any():
                    raise RuntimeError(f"collective {j} waits on itself")
                run_full(int(cprod[j]))
            e_j = q(t.max(axis=-1) + dur[..., j])           # (R, N)
            end = e_j[..., None]
            while True:       # slowed, until the collective ends or a gate
                active = ((ci < Kc) & (t < end)
                          & (wait[np.minimum(ci, Kc - 1)] < j))
                if not active.any():
                    break
                k = int(ci[active].min())
                m = active & (ci == k)
                begin(m, k)
                dt = rem_f / rf_s + rem_b / rm_s
                fits = m & (t + dt <= end)
                part = m & ~fits
                avail = end - t
                use = np.minimum(avail, rem_f / rf_s)
                rem_f = np.where(part, q(rem_f - use * rf_s), rem_f)
                rem_b = np.where(part, q(np.maximum(
                    0.0, rem_b - (avail - use) * rm_s)), rem_b)
                finish(fits, t + dt)
                t = np.where(part, end, t)
            t = np.broadcast_to(end, shape).copy()
            e_prev = e_j
        run_full(Kc - 1)
        t_local = np.maximum(t.max(axis=-1), e_prev)
        return t_local, q(busy / np.maximum(t_local[..., None], 1e-12))

    # ------------------------------------------------------------------ thermal
    def commit(self, temp, freq, cap, util, dt, r_th, m_coef):
        q, p = self.q, self.cfg["preset"]

        def m_eff(temp):
            over_ref = np.maximum(temp - p["t_ref"], 0.0)
            return m_coef * (1.0 + p["leak_quad"] * over_ref * over_ref)

        u_pow = 0.8 + 0.2 * np.clip(util, 0.0, 1.0)
        power = q(np.minimum(p["p_idle"] + m_eff(temp) * freq * u_pow, cap))
        t_ss = p["t_amb"] + r_th * power
        alpha = 1.0 - np.exp(-dt[..., None] / p["tau"])
        temp = q(temp + alpha * (t_ss - temp))
        budget = np.maximum(cap - p["p_idle"], 1.0)
        f_cap = budget / (m_eff(temp) * p["intensity"])
        f_hard = p["f_max"] * (1.0 - p["throttle_slope"]
                               * np.maximum(temp - p["t_throttle"], 0.0))
        freq = q(np.clip(np.minimum(f_cap, f_hard), p["f_min"], p["f_max"]))
        return temp, freq, power

    # --------------------------------------------------------------------- run
    def run(self, rows: List[dict], iterations: int) -> List[Dict[str, float]]:
        """Per-row tail means: t_fleet_s, throughput, lead_max_s,
        fleet_power_w.  Each row gives ``thermal_seed``, ``noise_seed`` and
        ``boosts`` (one per node)."""
        cfg, q = self.cfg, self.q
        p, fleet = cfg["preset"], cfg
        N, G = fleet["n_nodes"], cfg["devices_per_node"]
        lot = [thermal_lottery(p, N, G, r["thermal_seed"], r["boosts"])
               for r in rows]
        r_th = q(np.stack([a for a, _ in lot]))
        m_coef = q(np.stack([b for _, b in lot]))
        tab = self.tab
        base = tab["cbytes"] / (cfg["sim"]["comm_gbps"] * 1e9)
        noise = NoiseStream(np.stack([noise_key(r["noise_seed"])
                                      for r in rows]),
                            N, G, len(tab["gflop"]), len(tab["cbytes"]),
                            cfg["sim"]["noise"])
        allreduce = (2.0 * (N - 1) / N * tab["grad_bytes"]
                     / (fleet["inter_node_gbps"] * 1e9)) if N > 1 else 0.0
        shape = (len(rows), N, G)
        temp = np.full(shape, p["t_amb"] + 20.0)
        freq = np.full(shape, p["f_max"])
        tdp = np.full(shape, p["tdp"])
        cap = np.full(shape, float(cfg["caps_w"]))
        warm = cfg["warmup_iterations"]
        for i in range(warm):
            nc, nm = noise(i)
            t_local, util = self.iteration(freq, q(nc), q(base * nm))
            temp, freq, _ = self.commit(temp, freq, tdp, util, t_local,
                                        r_th, m_coef)
        series = {"t_fleet": [], "lead_max": [], "power": []}
        for i in range(iterations):
            nc, nm = noise(warm + 1 + i)
            t_local, util = self.iteration(freq, q(nc), q(base * nm))
            t_fleet = q(t_local.max(axis=1) + allreduce)          # (R,)
            util_eff = util * (t_local / t_fleet[:, None])[..., None]
            temp, freq, power = self.commit(
                temp, freq, cap, util_eff,
                np.broadcast_to(t_fleet[:, None], t_local.shape),
                r_th, m_coef)
            series["t_fleet"].append(t_fleet)
            series["lead_max"].append(t_local.max(axis=1)
                                      - t_local.min(axis=1))
            series["power"].append(power.sum(axis=(1, 2)))
        tf = np.stack(series["t_fleet"])[-TAIL:]
        lm = np.stack(series["lead_max"])[-TAIL:]
        pw = np.stack(series["power"])[-TAIL:]
        return [{"t_fleet_s": float(np.mean(tf[:, r])),
                 "throughput": float(np.mean(1.0 / tf[:, r])),
                 "lead_max_s": float(np.mean(lm[:, r])),
                 "fleet_power_w": float(np.mean(pw[:, r]))}
                for r in range(len(rows))]


def sweep_rows(config: dict, samples: Sequence[int], sweep_seed: int,
               scenario_seed: int) -> List[dict]:
    """The rows of ``samples`` and, last, the healthy reference row, as the
    sweep contract defines them."""
    fleet = config
    N = fleet["n_nodes"]
    boosts = [fleet["straggler_boost"] if n == fleet["straggler_node"]
              else fleet["healthy_boost"] for n in range(N)]
    rows = [{"thermal_seed": scenario_seed + k,
             "noise_seed": sweep_seed * 1_000_003 + k, "boosts": boosts}
            for k in samples]
    rows.append({"thermal_seed": scenario_seed,
                 "noise_seed": sweep_seed * 1_000_003 + 999_999_937,
                 "boosts": [1.0] * N})
    return rows
