"""Plain reference of the LLHR fleet rollout, in NumPy.

It imports nothing of the program.  From a deployment file
(``configs/<name>.json``) and the rollout's inputs it recomputes, frame by
frame, what the paper's planning tick produces:

1. mobility (waypoint drift plus the drawn jitter), Bernoulli failure and
   recovery, the battery gate, and delegation of a dead UAV's arrivals to
   the first survivor;
2. P2 positions: projected normalized gradient descent on the eq. (9)
   link objective plus the eq. (8d) separation hinge, keeping the best
   iterate, then the pairwise separation repair;
3. P1 powers (eq. 6-7) on every feasible link, and the eq. (5) rates;
4. the contiguous-block chain DP (P3) for every requested source;
5. the arrival-weighted frame latency, the aggregate eq. (11b) load,
   the powers tightened to the links the served placements use, the
   transmit and compute energy, and the battery carry.

``simulate`` runs in any floating dtype: float64 is the reference, and
bfloat16 (``ml_dtypes``) is the lower-precision control that the
comparison must reject.  Given ``forced_assign`` (a candidate's own
per-source placements) the tightened power, the load, the energy and the
battery follow those placements, and ``assign_cost`` prices them at the
reference's own rates, so that a near-tie that the candidate broke the
other way costs nothing.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

INF = np.inf


# ---------------------------------------------------------------------------
# constants from a deployment file
# ---------------------------------------------------------------------------


def cnn_costs(cnn: dict):
    """(compute MACs [L], weight bytes [L], activation bits [L], input
    bits) of a CNN by the paper's eq. (1)-(3); pools cost no MACs and no
    weights, and pass their output on."""
    spatial, channels = cnn["input_hw"], cnn["input_channels"]
    wb, ab = cnn.get("weight_bits", 32), cnn.get("act_bits_per_elem", 32)
    comp, mem, act = [], [], []
    for ly in cnn["layers"]:
        kind = ly["kind"]
        if kind in ("conv", "pool"):
            k, st, pad = ly["kernel"], ly.get("stride", 1), ly.get("padding", 0)
            z = (spatial + 2 * pad - k) // st + 1
            if kind == "conv":
                n_prev, n = ly["in_channels"], ly["out_channels"]
                comp.append(float(n_prev) * k * k * n * z * z)
                mem.append((float(n_prev) * k * k * n + n) * wb / 8.0)
                channels = n
            else:
                comp.append(0.0)
                mem.append(0.0)
            act.append(float(channels) * z * z * ab)
            spatial = z
        elif kind == "fc":
            n_prev, n = ly["in_features"], ly["out_features"]
            comp.append(float(n_prev) * n)
            mem.append((float(n_prev) * n + n) * wb / 8.0)
            act.append(float(n) * ab)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    input_bits = float(cnn["input_hw"] ** 2 * cnn["input_channels"] * 8)
    return (np.array(comp), np.array(mem), np.array(act), input_bits)


@dataclass(frozen=True)
class Deployment:
    """Every constant of one deployment file that the tick reads."""

    U: int
    compute: np.ndarray
    memory: np.ndarray
    act_bits: np.ndarray
    input_bits: float
    mem_cap: np.ndarray
    compute_cap: np.ndarray
    throughput: np.ndarray
    th_c: float            # eq. (7) threshold per squared metre
    snr_c: float           # eq. (5) received SNR per watt at 1 m
    rate_c: float          # bandwidth / ln 2
    p_max: float
    coeff: float           # eq. (9) per-link weight
    steps: int
    lr: float
    radius: float
    repair_iters: int
    drift: float
    p_fail: float
    p_recover: float
    hover_j: float
    kappa: float
    battery_j: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Deployment":
        U = int(cfg["uavs"])
        comp, mem, act, inp = cnn_costs(cfg["cnn"])
        dev = cfg["devices"]
        thr = np.array([dev["throughputs_macs_per_s"][i % len(
            dev["throughputs_macs_per_s"])] for i in range(U)])
        rs = cfg["rollout_spec"]
        r = cfg["radio"]
        noise = 10.0 ** (r["noise_density_dbm"] / 10.0) * 1e-3 \
            * r["bandwidth_hz"]
        spectral = r["packet_bits"] * math.log(2.0) / (r["bandwidth_hz"]
                                                       * r["tau"])
        ps = cfg["position_spec"]
        return cls(
            U=U, compute=comp, memory=mem, act_bits=act, input_bits=inp,
            mem_cap=np.full(U, dev["mem_bytes"] * dev["mem_frac"]),
            compute_cap=thr * rs["frame_s"], throughput=thr,
            th_c=noise * (math.exp(spectral) - 1.0) / r["h0"],
            snr_c=r["h0"] / noise, rate_c=r["bandwidth_hz"] / math.log(2.0),
            p_max=r["p_max_watts"],
            coeff=noise / r["h0"] * (math.exp(spectral) - 1.0),
            steps=int(ps["steps"]), lr=float(ps["lr"]),
            radius=float(ps["radius"]), repair_iters=int(ps["repair_iters"]),
            drift=rs["drift_m_per_frame"], p_fail=rs["failure_prob"],
            p_recover=rs["recovery_prob"],
            hover_j=rs["hover_watts"] * rs["frame_s"],
            kappa=rs["compute_j_per_mac"], battery_j=rs["battery_j"])


# ---------------------------------------------------------------------------
# one planning tick, batched over N independent trajectories
# ---------------------------------------------------------------------------


def _p2_terms(dep: Deployment, dt):
    """The penalized eq. (9) objective (chain links i - i+1, plus the
    eq. (8d) separation hinge) of positions [N, U, 2], and its gradient."""
    U = dep.U
    eye = np.eye(U, dtype=bool)
    links = np.zeros((U, U), dtype=bool)
    links[np.arange(U - 1), np.arange(1, U)] = True
    links = links | links.T
    coeff = dt(dep.coeff)
    two_r = dt(2.0 * dep.radius)

    def pair(p):
        rel = p[:, :, None, :] - p[:, None, :, :]               # [N,U,U,2]
        return rel, (rel * rel).sum(-1)

    def objective(p):
        _, d2 = pair(p)
        obj = np.where(links, coeff * d2, dt(0)).sum((1, 2)) / dt(2)
        viol = np.maximum(two_r * two_r - d2, dt(0))
        pen = np.where(eye, dt(0), viol * viol).sum((1, 2))
        return obj + dt(10) * coeff * pen

    def grad(p):
        rel, d2 = pair(p)
        viol = np.where(eye, dt(0), np.maximum(two_r * two_r - d2, dt(0)))
        w = np.where(links, dt(2) * coeff, dt(0)) - dt(80) * coeff * viol
        return (w[..., None] * rel).sum(2)

    return objective, grad


def _positions(dep: Deployment, pos, dt):
    """P2 from the frame's positions [N, U, 2]: the best iterate of the
    projected normalized descent, then the separation repair."""
    N, U = pos.shape[0], dep.U
    eye = np.eye(U, dtype=bool)
    objective, grad = _p2_terms(dep, dt)
    lr, two_r = dt(dep.lr), dt(2.0 * dep.radius)
    cover_r = dt(max(dep.radius, 2.0 * dep.radius * (math.sqrt(U) + 1.0)))
    center = pos.mean(axis=1)                                   # [N, 2]

    def project(p):
        rel = p - center[:, None, :]
        r = np.sqrt((rel * rel).sum(-1, keepdims=True))
        return center[:, None, :] + rel * np.minimum(
            dt(1), cover_r / np.maximum(r, dt(1e-9)))

    p = project(pos)
    best_p, best_obj = p, objective(p)
    for _ in range(dep.steps):
        g = grad(p)
        gn = np.sqrt((g * g).sum((1, 2), keepdims=True))
        p = project(p - lr * g / (gn + dt(1e-12)))
        obj = objective(p)
        better = obj < best_obj
        best_p = np.where(better[:, None, None], p, best_p)
        best_obj = np.minimum(obj, best_obj)
    p = best_p.copy()
    rows = np.arange(N)
    for _ in range(dep.repair_iters):
        rel = p[:, :, None, :] - p[:, None, :, :]
        d = np.where(eye, dt(INF), np.sqrt((rel * rel).sum(-1)))
        d = d.reshape(N, -1)
        arg = d.argmin(-1)
        i, k = arg // U, arg % U
        pi, pk = p[rows, i], p[rows, k]
        mid = (pi + pk) / dt(2)
        dirv = pi - pk
        nrm = np.sqrt((dirv * dirv).sum(-1, keepdims=True))
        dirv = np.where(nrm < dt(1e-6), np.array([1.0, 0.0], dtype=dt),
                        dirv / (nrm + dt(1e-9)))
        push = dirv * (two_r / dt(2) + dt(1e-3))
        need = (d.min(-1) < two_r - dt(1e-6))[:, None]
        p[rows, i] = np.where(need, mid + push, pi)
        p[rows, k] = np.where(need, mid - push, pk)
    return p


def _geometry(dep: Deployment, pos, active, dt):
    """Distances, eq. (7) thresholds, the feasible-link mask and the
    eq. (5) rates at the first-pass P1 powers."""
    U = dep.U
    eye = np.eye(U, dtype=bool)
    rel = pos[:, :, None, :] - pos[:, None, :, :]
    dist = np.sqrt((rel * rel).sum(-1))
    d2 = np.maximum(dist, dt(1)) ** 2
    th = np.where(eye, dt(0), d2 * dt(dep.th_c))
    feas = (th <= dt(dep.p_max)) & \
        ((active[:, :, None] & active[:, None, :]) | eye)
    power = _row_power(dep, th, feas & ~eye, active, dt)
    rate = np.log(dt(1) + power[:, :, None] * dt(dep.snr_c) / d2) \
        * dt(dep.rate_c)
    rate = np.where(feas, rate, dt(0))
    rate = np.where(eye, dt(INF), rate)
    return d2, th, feas, rate


def _row_power(dep, th, use, active, dt):
    """eq. (6): each UAV's power is its largest used-link threshold,
    clamped to P_max; a dead UAV transmits nothing."""
    p = np.minimum(np.where(use, th, dt(0)).max(-1), dt(dep.p_max))
    return np.where(active, p, dt(0))


def _chain_dp(dep: Deployment, rate, source, active, dt, backtrack=True):
    """The contiguous-block chain DP for one source per row.

    Blocks of consecutive layers go to distinct UAVs in index order; a
    block on UAV s pays its transfer in (the input from the source, or
    the previous block's activations) plus its compute time, within the
    UAV's memory and per-period compute caps.  rate [N, U, U], source
    [N], active [N, U] -> (assign [N, L], latency [N]); ties go to the
    first block start, then the first predecessor, then the first final
    UAV."""
    N, U, L = rate.shape[0], dep.U, len(dep.compute)
    pre_c = np.concatenate([[0.0], np.cumsum(dep.compute)])
    pre_m = np.concatenate([[0.0], np.cumsum(dep.memory)])
    bits_in = np.concatenate([[dep.input_bits], dep.act_bits[:-1]])
    rows = np.arange(N)
    with np.errstate(divide="ignore"):
        r_src = rate[rows, source]                               # [N, U]
        tr_src = np.where(r_src > 0, dt(dep.input_bits) / r_src, dt(INF))
    dp = np.full((N, L + 1, U + 1), INF, dtype=dt)
    dp[:, 0, 0] = 0
    par_a = np.zeros((N, L + 1, U + 1), np.int64)
    par_s = np.zeros((N, L + 1, U + 1), np.int64)
    for b in range(1, L + 1):
        for s in range(1, U + 1):
            u = s - 1
            best = np.full(N, INF, dtype=dt)
            ba = np.zeros(N, np.int64)
            bs = np.zeros(N, np.int64)
            for a in range(b):
                blk_c = pre_c[b] - pre_c[a]
                blk_m = pre_m[b] - pre_m[a]
                if blk_m > dep.mem_cap[u] + 1e-9 or \
                        blk_c > dep.compute_cap[u] + 1e-9:
                    continue
                ct = dt(blk_c / dep.throughput[u])
                if a == 0:
                    cands = [(0, dp[:, 0, 0] + tr_src[:, u])]
                else:
                    r = rate[:, :s - 1, u]                       # [N, s-1]
                    with np.errstate(divide="ignore"):
                        tr = np.where(r > 0, dt(bits_in[a]) / r, dt(INF))
                    cands = [(s0, dp[:, a, s0] + tr[:, s0 - 1])
                             for s0 in range(1, s)]
                for s0, c in cands:
                    c = np.where(active[:, u], c + ct, dt(INF))
                    better = c < best
                    best = np.where(better, c, best)
                    ba = np.where(better, a, ba)
                    bs = np.where(better, s0, bs)
            dp[:, b, s] = best
            par_a[:, b, s] = ba
            par_s[:, b, s] = bs
    final = dp[:, L, :]
    s_best = final.argmin(-1)
    latency = final[rows, s_best]
    assign = np.full((N, L), -1, np.int64)
    if not backtrack:
        return assign, latency
    for n in np.flatnonzero(np.isfinite(latency)):
        b, s = L, int(s_best[n])
        while b > 0 and s > 0:
            a = int(par_a[n, b, s])
            assign[n, a:b] = s - 1
            b, s = a, int(par_s[n, b, s])
    return assign, latency


def _assign_cost(dep: Deployment, rate, assign, source, active, dt):
    """What a given placement costs at these rates (inf where it breaks a
    rule the DP keeps: contiguous blocks on distinct UAVs in index order,
    the memory and compute caps, live UAVs, usable links)."""
    N, L = assign.shape
    bits_in = np.concatenate([[dep.input_bits], dep.act_bits[:-1]])
    out = np.full(N, INF, dtype=dt)
    for n in range(N):
        a = assign[n]
        if (a < 0).any():
            continue
        cost, ok, start = dt(0), True, 0
        prev_dev = -1
        for j in range(1, L + 1):
            if j < L and a[j] == a[start]:
                continue
            u = int(a[start])
            blk_c = dep.compute[start:j].sum()
            blk_m = dep.memory[start:j].sum()
            src = int(source[n]) if start == 0 else prev_dev
            if u <= prev_dev or not active[n, u] or \
                    blk_m > dep.mem_cap[u] + 1e-9 or \
                    blk_c > dep.compute_cap[u] + 1e-9:
                ok = False
                break
            r = rate[n, src, u]
            if src != u:
                if not r > 0:
                    ok = False
                    break
                cost = cost + dt(bits_in[start]) / r
            cost = cost + dt(blk_c / dep.throughput[u])
            prev_dev, start = u, j
        if ok:
            out[n] = cost
    return out


def _links(assign, source, U):
    """[N, U, U] links a placement uses: source -> first block, then every
    change of UAV along the chain."""
    N, L = assign.shape
    used = np.zeros((N, U, U), dtype=bool)
    prev = np.concatenate([source[:, None], assign[:, :-1]], axis=1)
    hop = (prev >= 0) & (assign >= 0) & (prev != assign)
    n_ix, j_ix = np.nonzero(hop)
    used[n_ix, prev[n_ix, j_ix], assign[n_ix, j_ix]] = True
    return used


def _tx_time(dep, rate, assign, source, dt):
    """[N, U] airtime one request of this placement costs each
    transmitter."""
    N, L = assign.shape
    bits_in = np.concatenate([[dep.input_bits], dep.act_bits[:-1]])
    prev = np.concatenate([source[:, None], assign[:, :-1]], axis=1)
    hop = (prev >= 0) & (assign >= 0) & (prev != assign)
    tx = np.zeros((N, dep.U), dtype=dt)
    for n, j in zip(*np.nonzero(hop)):
        r = rate[n, prev[n, j], assign[n, j]]
        if r > 0:
            tx[n, prev[n, j]] += dt(bits_in[j]) / r
    return tx


def simulate(dep: Deployment, inputs: dict, dt=np.float64,
             forced=None) -> dict:
    """Roll N trajectories through T frames.

    ``inputs``: pos0 [N, U, 2], waypoint [N, U, 2], charge0 [N, U],
    alive0 [N, U], jitter [T, N, U, 2], fail_u / recov_u / arrivals
    [T, N, U].  ``forced``: a candidate's own ``positions`` [N, T, U, 2]
    and ``assign`` [N, T, U, L].  With it each frame moves from the
    candidate's previous positions, prices the candidate's P2 positions
    against its own P2 result (``p2_obj`` and ``p2_obj_ref``, the
    penalized eq. (9) objective of each), plans at the candidate's
    positions, prices the candidate's placements at its own rates
    (``assign_cost``), and tightens, loads and drains by them.  Without
    it the run follows its own results.  Returns per-frame stacks,
    trajectory-major ([N, T, ...]).
    """
    U, L = dep.U, len(dep.compute)
    f = lambda x: np.asarray(x, np.float64).astype(dt)       # noqa: E731
    pos = f(inputs["pos0"])
    waypoint = f(inputs["waypoint"])
    charge = f(inputs["charge0"])
    alive = np.asarray(inputs["alive0"], bool).copy()
    T, N = inputs["arrivals"].shape[:2]
    rows = np.arange(N)
    comp = dep.compute.astype(dt)
    eye = np.eye(U, dtype=bool)
    out = {k: [] for k in ("positions", "active", "charge", "latency",
                           "total_power", "feasible", "cap_ok", "assign",
                           "source_latency", "n_requests", "energy_tx",
                           "energy_cmp", "assign_cost", "p2_obj",
                           "p2_obj_ref")}
    for t in range(T):
        # 1. mobility
        to_wp = waypoint - pos
        nrm = np.sqrt((to_wp * to_wp).sum(-1, keepdims=True))
        pos = pos + to_wp * np.minimum(dt(1), dt(dep.drift) / np.maximum(
            nrm, dt(1e-9))) + f(inputs["jitter"][t])
        # 2. failure and recovery (a UAV failing now stays down a frame)
        fail_u = np.asarray(inputs["fail_u"][t], np.float32)
        rec_u = np.asarray(inputs["recov_u"][t], np.float32)
        revived = ~alive & (rec_u < np.float32(dep.p_recover))
        alive = (alive & (fail_u >= np.float32(dep.p_fail))) | revived
        # 3. battery gate
        active = alive & (charge > 0)
        # 4. a dead UAV's arrivals go to the first survivor
        arr = np.asarray(inputs["arrivals"][t], np.float64)
        first = active.argmax(-1)
        n_eff = np.where(active, arr, 0.0)
        n_eff[rows, first] += (arr - n_eff).sum(-1)
        requested = n_eff > 0
        cnt = f(n_eff)
        # 5. P2, then the planning tick at the positions it chose
        own = _positions(dep, pos, dt)
        pos = own if forced is None else f(forced["positions"][:, t])
        p2_objective = _p2_terms(dep, dt)[0]
        obj, obj_ref = p2_objective(pos), p2_objective(own)
        d2, th, feas, rate = _geometry(dep, pos, active, dt)
        # every source's DP in one stacked call: row n * U + u
        rate_all = np.repeat(rate, U, axis=0)
        act_all = np.repeat(active, U, axis=0)
        src_all = np.tile(np.arange(U), N)
        a_all, l_all = _chain_dp(dep, rate_all, src_all, act_all, dt,
                                 backtrack=forced is None)
        assign = a_all.reshape(N, U, L)
        lat_src = np.where(requested, l_all.reshape(N, U), dt(INF))
        cost = np.full((N, U), INF, dtype=dt)
        if forced is not None:
            assign = np.asarray(forced["assign"][:, t], np.int64)
            cost = _assign_cost(dep, rate_all, assign.reshape(N * U, L),
                                src_all, act_all, dt).reshape(N, U)
        assign = np.where(requested[..., None], assign, -1)
        with np.errstate(invalid="ignore"):
            weighted = np.where(requested, cnt * lat_src, dt(0)).sum(-1)
        latency = weighted / np.maximum(cnt.sum(-1), dt(1))
        latency = np.where((requested & ~np.isfinite(lat_src)).any(-1),
                           dt(INF), latency)
        onehot = assign[..., None] == np.arange(U)               # [N,U,L,U]
        macs = (comp[None, None, :, None] * onehot).sum(2)       # [N, U, U]
        load = (macs * cnt[..., None]).sum(1)                    # [N, U]
        cap_ok = (load <= dep.compute_cap * (1.0 + 1e-6) + 1e-9).all(-1)
        latency = np.where(cap_ok, latency, dt(INF))
        feasible = np.isfinite(latency)
        # 6. tightened powers, energy and the battery carry
        served = requested & (assign >= 0).all(-1)
        used = np.zeros((N, U, U), dtype=bool)
        tx = np.zeros((N, U), dtype=dt)
        for u in range(U):
            src = np.full(N, u)
            a_u = np.where(served[:, u, None], assign[:, u], -1)
            used |= _links(a_u, src, U)
            tx = tx + _tx_time(dep, rate, a_u, src, dt) * cnt[:, u, None]
        power = _row_power(dep, th, used & feas & ~eye, active, dt)
        e_cmp = np.where(feasible[:, None], dt(dep.kappa) * load, dt(0))
        e_tx = np.where(feasible[:, None], power * tx, dt(0))
        drain = np.where(active, e_cmp + e_tx + dt(dep.hover_j), dt(0))
        charge = np.maximum(charge - drain, dt(0))
        for k, v in (("positions", pos), ("active", active),
                     ("charge", charge), ("latency", latency),
                     ("total_power",
                      np.where(feasible, power.sum(-1), dt(0))),
                     ("feasible", feasible), ("cap_ok", cap_ok),
                     ("assign", assign), ("source_latency", lat_src),
                     ("n_requests", n_eff), ("energy_tx", e_tx),
                     ("energy_cmp", e_cmp), ("assign_cost", cost),
                     ("p2_obj", obj), ("p2_obj_ref", obj_ref)):
            out[k].append(np.asarray(v))
    return {k: np.stack(v, 1) for k, v in out.items()}


__all__ = ["Deployment", "cnn_costs", "simulate"]
