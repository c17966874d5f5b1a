"""Plain reference of one what-if cell: trace, cell inputs, fluid model.

A straightforward, per-cell re-statement of what the system under test
answers for one (deployment, policy, seed) cell, written from the model's
description and sharing no code with it:

1. the SWIM-recipe job stream (Poisson arrivals with optional diurnal
   thinning and bursts, a weighted workload draw, heavy-tailed input
   sizes, per-workload deadlines), drawn from ``random.Random`` exactly as
   the recipe orders its draws; or, for a deployment published as a table
   of job types, the rows ``harness.jobtypes`` draws from the seed, which
   the program is handed as they are;
2. the cell inputs: 128 MB blocks per map task, per-workload reduce
   fractions, each HDFS block on ``replication`` distinct VMs, and a
   per-job duration jitter from ``numpy``'s generator;
3. the discrete-lag fluid model: pending task mass launched into free
   slots by the policy's ordering, held in per-job in-flight delay rings
   for its quantized service time, with locality draws, parking,
   delay scheduling, fabric contention and the overload latch;
4. what a user reads of the cell: each job's finish time and the cell's
   locality rate (local over all map launch mass).

It integrates one cell at a time over its real jobs (no padding, no
batching, no early-exit chunks) with plain ``numpy``.  Every array holds
``dtype``: float32 is the precision the model states; bfloat16 is the
lower-precision control, which a sound comparison must reject.

It is the reference of every configuration that names no other
(``harness.registry``).  A reference exposes ``CLUSTER_KEYS``, ``lower``,
``build``, ``answer`` and ``deadline`` (``registry.REFERENCE_API``).  It
may also expose ``EXTRA_NUMBERS``, names its ``answer`` returns besides
these, with ``record_numbers(rec)``, which reads them off a program
``RunRecord`` (``registry.OPTIONAL_API``); the check then compares each.
This one adds none.
"""
from __future__ import annotations

import hashlib
import json
import math
import random
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from harness import jobtypes

# -- the paper's five workloads (section 5), as the model calibrates them --
#: name -> (map s, reduce s, shuffle s per mapper-reducer pair,
#: remote-read penalty, duration coefficient of variation)
BASE_COPY = 0.012
WORKLOADS: Dict[str, tuple] = {
    "grep": (20.0, 8.0, BASE_COPY * 0.2, 1.0, 0.08),
    "wordcount": (30.0, 12.0, BASE_COPY, 1.0, 0.08),
    "sort": (22.0, 20.0, BASE_COPY * 1.6, 1.0, 0.08),
    "permutation": (25.0, 35.0, BASE_COPY * 4.0, 1.0, 0.08),
    "inverted_index": (35.0, 15.0, BASE_COPY * 1.2, 1.0, 0.08),
}
#: reduce tasks per map task
REDUCE_FRACTION = {"grep": 0.15, "wordcount": 0.25, "sort": 0.5,
                   "permutation": 0.6, "inverted_index": 0.3}

# -- the fluid model's constants ---------------------------------------------
DT = 6.0                 # integrator step, simulated seconds
RING = 64                # in-flight ring depth, steps
EPS = 1e-6
INF = 3.0e9              # "not finished"
TAIL_INFLATION = 1.04    # straggler inflation net of speculation
PARK_SUCCESS = 1.0
PARK_WAIT = 6.0
PARK_CROWD_PENALTY = 1.0
PARK_WAIT_CROWD = 0.5
REPARK_CROWD = 6.0
SAT_LO = 0.75
SAT_WIDTH = 0.3
LOCALITY_DRAWS = 8.0
DELAY_BOOST = 0.35
DELAY_REMOTE_WAIT = 2.0
NET_CONTENTION = 1.25
FAIR_ITERS = 8

#: the configuration ``cluster`` keys this model reads, and so the only
#: ones a deployment it checks may set
CLUSTER_KEYS = ("num_machines", "vms_per_machine", "base_map_slots",
                "base_reduce_slots", "replication", "remote_penalty_scale",
                "overload_pending_factor", "overload_active_factor")

#: policy name -> (ordering, park, overload, default params); ordering
#: 0 = earliest deadline, 1 = submission order, 2 = fair share
POLICIES: Dict[str, tuple] = {
    "proposed": (0, 1, 0, {"max_wait": 30.0}),
    "edf_nopark": (0, 0, 0, {"max_wait": 30.0}),
    "fair": (2, 0, 0, {"locality_delay": 0}),
    "delay": (2, 0, 0, {"locality_delay": 8}),
    "fifo": (1, 0, 0, {}),
}


def stable_seed(*parts) -> int:
    """Integer seed from the canonical JSON of ``parts`` (sha256)."""
    blob = json.dumps(list(parts), sort_keys=True, separators=(",", ":"))
    return int.from_bytes(hashlib.sha256(blob.encode()).digest()[:8], "big")


def map_tasks(gb: float) -> int:
    return max(1, int(math.ceil(gb * 8)))


def reduce_tasks(workload: str, gb: float) -> int:
    return max(1, int(round(map_tasks(gb) * REDUCE_FRACTION[workload])))


def deadline(workload: str, gb: float, slack: float) -> float:
    m, r, s = WORKLOADS[workload][:3]
    u, v = map_tasks(gb), reduce_tasks(workload, gb)
    return slack * (u * m / 20.0 + v * (r + u * s) / 10.0) + 120.0


# ---------------------------------------------------------------------------
# 1. the job stream
# ---------------------------------------------------------------------------

def _rate(arr: dict, t: float) -> float:
    base = arr["rate_per_hour"] / 3600.0
    if arr["diurnal_amplitude"] <= 0:
        return base
    return base * (1.0 + arr["diurnal_amplitude"] * math.sin(
        2.0 * math.pi * (t + arr["diurnal_phase_s"])
        / arr["diurnal_period_s"]))


def _arrivals(arr: dict, rng: random.Random, n: int) -> List[float]:
    lam_max = arr["rate_per_hour"] / 3600.0 * (1.0 + arr["diurnal_amplitude"])
    times: List[float] = []
    t = 0.0
    while len(times) < n:
        t += rng.expovariate(lam_max)
        if rng.random() * lam_max > _rate(arr, t):
            continue
        times.append(t)
        if arr["burst_prob"] > 0 and rng.random() < arr["burst_prob"]:
            p = 1.0 / max(1.0, arr["burst_size_mean"])
            extra = 0
            while rng.random() > p:
                extra += 1
            for k in range(extra):
                if len(times) >= n:
                    break
                times.append(t + (k + 1) * arr["burst_stagger_s"])
    times.sort()
    return times[:n]


def _size(sizes: dict, rng: random.Random) -> float:
    if sizes["distribution"] == "lognormal":
        gb = rng.lognormvariate(math.log(sizes["median_gb"]), sizes["sigma"])
    else:
        gb = sizes["min_gb"] * rng.paretovariate(sizes["alpha"])
    return round(min(sizes["max_gb"], max(sizes["min_gb"], gb)), 3)


def job_stream(trace: dict, seed: int) -> List[dict]:
    """The jobs of the trace recipe ``trace`` at ``seed``, in arrival
    order: workload, input GB, submit time and relative deadline."""
    rng = random.Random(stable_seed("repro-trace", trace, seed))
    names = [w for w, _ in trace["mix"]]
    weights = [x for _, x in trace["mix"]]
    jobs = []
    for i, t in enumerate(_arrivals(trace["arrival"], rng,
                                    trace["num_jobs"])):
        w = rng.choices(names, weights=weights)[0]
        gb = _size(trace["sizes"], rng)
        jobs.append({
            "job_id": f"{trace['name']}-{i:04d}-{w}", "workload": w,
            "input_gb": gb, "submit": round(t, 3),
            "deadline": round(deadline(w, gb, trace["deadline_slack"]), 3)})
        rng.randrange(1 << 31)   # the job's block-placement seed
    return jobs


# ---------------------------------------------------------------------------
# 2. cell inputs
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    """One cell's per-job arrays (real jobs only) and scalars."""

    jobs: List[dict]
    submit: np.ndarray
    dl_rel: np.ndarray
    u_m: np.ndarray
    v_r: np.ndarray
    map_t: np.ndarray
    red_t: np.ndarray
    c_over_n: float
    remote_mult: float
    map_slots: float
    red_slots: float
    machines: float
    pending_bar: float
    active_bar: float
    ordering: int
    park: int
    overload: int
    locality_delay: float
    max_wait: float
    n_steps: int


def lower(policy: dict) -> tuple:
    """(ordering, park, overload, locality_delay, max_wait) of a policy
    given as ``{"name": ..., "params": {...}}``."""
    ordering, park, overload, defaults = POLICIES[policy["name"]]
    params = dict(defaults)
    params.update(policy.get("params", {}))
    delay = float(params.get("locality_delay", 0) or 0)
    max_wait = float(params.get("max_wait", 30.0)) if park else 0.0
    return ordering, park, overload, delay, max_wait


def build(config: dict, policy: dict, seed: int) -> Cell:
    """Cell inputs of ``policy`` on the deployment ``config`` at the trace
    seed ``seed``."""
    trace, cluster = config["trace"], config["cluster"]
    if jobtypes.is_job_types(trace):
        name = jobtypes.trace_name(trace, seed)
        jobs = [{"job_id": f"{name}-{i:04d}-{w}", "workload": w,
                 "input_gb": gb, "submit": t, "deadline": dl}
                for i, (w, gb, dl, t) in enumerate(
                    jobtypes.rows(trace, seed, deadline))]
        jitter = (name, seed, jobtypes.SIM_SEED)
    else:
        jobs = job_stream(trace, seed)
        jitter = (trace["name"], seed, seed)
    nodes = cluster["num_machines"] * cluster["vms_per_machine"]
    rng = np.random.default_rng(stable_seed("surrogate-jitter", *jitter))
    n = len(jobs)
    map_t = np.empty(n, np.float32)
    red_t = np.empty(n, np.float32)
    u_m = np.empty(n, np.float32)
    v_r = np.empty(n, np.float32)
    for i, job in enumerate(jobs):
        m, r, s, _, cv = WORKLOADS[job["workload"]]
        u, v = map_tasks(job["input_gb"]), reduce_tasks(job["workload"],
                                                        job["input_gb"])
        z_m, z_r = rng.standard_normal(2)
        map_t[i] = m * TAIL_INFLATION * math.exp(cv * z_m / math.sqrt(u))
        red_t[i] = ((r + u * s) * TAIL_INFLATION
                    * math.exp(cv * z_r / math.sqrt(v)))
        u_m[i], v_r[i] = u, v
    submit = np.array([j["submit"] for j in jobs], np.float32)
    remote_mult = 1.0 + WORKLOADS[jobs[0]["workload"]][3] \
        * cluster["remote_penalty_scale"]
    map_slots = float(nodes * cluster["base_map_slots"])
    red_slots = float(nodes * cluster["base_reduce_slots"])
    work = (float(np.sum(u_m * map_t)) * remote_mult / map_slots
            + float(np.sum(v_r * red_t)) / red_slots)
    horizon = float(np.max(submit)) + 3.0 * work + 900.0
    steps = 256
    while steps < int(math.ceil(horizon / DT)):
        steps *= 2
    ordering, park, overload, delay, max_wait = lower(policy)
    return Cell(
        jobs=jobs, submit=submit,
        dl_rel=np.array([j["deadline"] for j in jobs], np.float32),
        u_m=u_m, v_r=v_r, map_t=map_t, red_t=red_t,
        # each block lives on `replication` distinct VMs
        c_over_n=min(np.float32(min(cluster["replication"], nodes))
                     / np.float32(nodes), np.float32(0.999)),
        remote_mult=remote_mult, map_slots=map_slots, red_slots=red_slots,
        machines=float(cluster["num_machines"]),
        pending_bar=cluster["overload_pending_factor"] * map_slots,
        active_bar=cluster["overload_active_factor"]
        * cluster["num_machines"],
        ordering=ordering, park=park, overload=overload,
        locality_delay=delay, max_wait=max_wait, n_steps=steps)


# ---------------------------------------------------------------------------
# 3. the fluid model
# ---------------------------------------------------------------------------

def _fair(q, demand, capacity):
    """Equal-share progressive filling of ``capacity`` over ``demand``."""
    alloc = q(np.zeros_like(demand))
    for _ in range(FAIR_ITERS):
        need = q(demand - alloc)
        unsat = q(need > EPS)
        n_unsat = q(max(np.sum(unsat), 1.0))
        share = q(q(max(capacity - np.sum(alloc), 0.0)) / n_unsat)
        alloc = q(alloc + q(np.minimum(need, share) * unsat))
    return alloc


def _priority(q, demand, capacity, order, inv):
    """Jobs take their whole demand in priority order until capacity
    runs out."""
    d = demand[order]
    before = q(q(np.cumsum(d, dtype=d.dtype)) - d)
    return q(np.clip(q(capacity - before), 0.0, d))[inv]


def integrate(cell: Cell, dtype=np.float32) -> Dict[str, np.ndarray]:
    """Advance the cell step by step until every job has finished or the
    horizon ends.  Returns per-job ``finish`` (``INF`` if unfinished),
    ``local`` and ``remote`` map launch mass, and ``latched_steps``."""
    def q(x):
        return np.asarray(x, dtype)

    n = len(cell.jobs)
    rows = np.arange(n)
    dt = q(DT)
    submit = q(cell.submit)
    dl_abs = q(submit + q(cell.dl_rel))

    def lag(seconds):
        return np.clip(np.round(q(seconds / dt)), 1, RING - 1).astype(
            np.int32)

    lag_ml = lag(q(cell.map_t))
    lag_mr = lag(q(q(cell.map_t) * q(cell.remote_mult)))
    lag_rr = lag(q(cell.red_t))
    prio = submit if cell.ordering == 1 else dl_abs
    order = np.argsort(prio, kind="stable")
    inv = np.argsort(order, kind="stable")
    log_miss = q(np.log1p(q(-cell.c_over_n)) * np.ones(n, dtype))
    fair_order = cell.ordering == 2
    max_wait = q(cell.max_wait)
    delay = q(cell.locality_delay)
    ell = q(1.0 + q(DELAY_BOOST * delay))
    lf_base = q(1.0 - q(np.exp(q(q(ell * LOCALITY_DRAWS) * log_miss))))
    delay_lag = int(np.round(q(q(DELAY_REMOTE_WAIT * delay) / dt)))
    slots_m, slots_r = q(cell.map_slots), q(cell.red_slots)

    pend_m, pend_r = q(cell.u_m), q(cell.v_r)
    ring_m, ring_r, park_s, park_x = (np.zeros((n, RING), dtype)
                                      for _ in range(4))
    finish = q(np.full(n, INF))
    loc_acc, rem_acc = q(np.zeros(n)), q(np.zeros(n))
    latch, lsteps = False, 0

    def alloc(fair, demand, capacity):
        if fair:
            return _fair(q, demand, capacity)
        return _priority(q, demand, capacity, order, inv)

    for it in range(cell.n_steps):
        t = q(q(it) * dt)
        submitted = q(submit <= t)
        idx = it % RING
        ring_m[:, idx] = 0.0
        ring_r[:, idx] = 0.0
        mat_s, mat_x = park_s[:, idx].copy(), park_x[:, idx].copy()
        park_s[:, idx] = 0.0
        park_x[:, idx] = 0.0
        inflight_m = q(ring_m.sum(axis=1, dtype=dtype))
        inflight_r = q(ring_r.sum(axis=1, dtype=dtype))
        waiting = q(park_s.sum(axis=1, dtype=dtype)
                    + park_x.sum(axis=1, dtype=dtype))
        map_left = q(pend_m + inflight_m + waiting + mat_s + mat_x)
        red_left = q(pend_r + inflight_r)
        map_open = q(submitted * (map_left > EPS))
        red_open = q(submitted * (map_left <= EPS) * (red_left > EPS))
        pending = q(np.sum(q(pend_m * submitted)))
        active = q(np.sum(q(submitted * ((map_left > EPS)
                                         | (red_left > EPS)))))
        trip = (pending >= q(cell.pending_bar)
                and active >= q(cell.active_bar))
        latch = bool(cell.overload) and (latch or trip) and active > 0.5
        fair = fair_order or latch
        park_on = bool(cell.park) and not latch
        chi_raw = q(active / q(cell.machines))
        chi = q(np.clip(chi_raw, 0.0, 1.0))
        # -- maps: a share-capped round, then a backfill round
        free_m = q(max(q(slots_m - np.sum(inflight_m) - np.sum(waiting)),
                       0.0))
        share = q(slots_m / q(max(np.sum(map_open), 1.0)))
        cap = q(np.maximum(q(share - waiting), 0.0))
        launch1 = alloc(fair, q(np.minimum(pend_m, cap) * map_open), free_m)
        spare = q(max(q(free_m - np.sum(launch1)), 0.0))
        launch2 = alloc(fair, q(np.maximum(q(pend_m - launch1), 0.0)
                                * map_open), spare)
        launch = q(launch1 + launch2)
        launch_loc = q(launch * lf_base)
        rest = q(launch - launch_loc)
        # -- parking: crowd-degraded odds and waits
        wait_eff = q(min(q(PARK_WAIT * q(1.0 + q(PARK_WAIT_CROWD * chi))),
                         max_wait))
        p_succ = q(PARK_SUCCESS * q(max(q(1.0 - q(PARK_CROWD_PENALTY
                                                  * chi)), 0.0)))
        ws = int(np.round(q(wait_eff / dt)))
        saturate = q(np.clip(q(q(chi_raw - SAT_LO) / SAT_WIDTH), 0.0, 1.0))
        wx = min(int(np.round(q(q(max_wait * q(1.0 + q(REPARK_CROWD
                                                       * saturate))) / dt))),
                 RING - 1)
        crit = q(dl_abs - t) <= q(3.0 * max_wait)
        park_f = q(float(park_on) * q(1.0 - crit))
        f_psucc = q(q(rest * park_f) * p_succ)
        f_pexp = q(q(rest * park_f) * q(1.0 - p_succ))
        f_rem = q(rest * q(1.0 - park_f))
        # -- remote reads launched together contend on the fabric
        rem_load = q(q(np.sum(q(f_rem + mat_x))) / slots_m)
        lag_mr_eff = np.minimum(
            lag_mr + delay_lag + np.round(q(q(lag_mr.astype(dtype)
                                              * NET_CONTENTION) * rem_load)
                                          ).astype(np.int32), RING - 1)
        ring_m[rows, (it + lag_ml) % RING] += q(launch_loc + mat_s)
        ring_m[rows, (it + lag_mr_eff) % RING] += q(f_rem + mat_x)
        park_s[:, (it + ws) % RING] += f_psucc
        park_x[:, (it + wx) % RING] += f_pexp
        pend_m = q(np.maximum(q(pend_m - launch), 0.0))
        pend_m[pend_m <= 0.01] = 0.0
        loc_acc = q(q(loc_acc + launch_loc) + f_psucc)
        rem_acc = q(q(rem_acc + f_rem) + f_pexp)
        # -- reduces, after the job's maps drain
        free_r = q(max(q(slots_r - np.sum(inflight_r)), 0.0))
        launch_r = alloc(fair, q(pend_r * red_open), free_r)
        ring_r[rows, (it + lag_rr) % RING] += launch_r
        pend_r = q(np.maximum(q(pend_r - launch_r), 0.0))
        pend_r[pend_r <= 0.01] = 0.0
        # -- completions
        map_left = q(pend_m + inflight_m + launch_loc + mat_s + f_rem
                     + mat_x + waiting + f_psucc + f_pexp)
        red_left = q(pend_r + inflight_r + launch_r)
        done = (submitted > 0.5) & (map_left <= EPS) & (red_left <= EPS)
        finish = np.where(done & (finish >= q(INF)), q(t + dt), finish)
        lsteps += int(latch)
        if np.all(finish < q(INF)):
            break
    return {"finish": finish, "local": loc_acc, "remote": rem_acc,
            "latched_steps": float(lsteps)}


# ---------------------------------------------------------------------------
# 4. what a user reads
# ---------------------------------------------------------------------------

def answer(config: dict, policy: dict, seed: int,
           dtype=np.float32, cell: Optional[Cell] = None) -> dict:
    """The cell's answer as a user reads it: the jobs, each job's finish
    time (NaN if unfinished) and the cell's locality rate."""
    cell = cell or build(config, policy, seed)
    out = integrate(cell, dtype)
    finish = np.asarray(out["finish"], np.float64)
    local = float(np.sum(np.asarray(out["local"], np.float64)))
    remote = float(np.sum(np.asarray(out["remote"], np.float64)))
    return {
        "job_ids": [j["job_id"] for j in cell.jobs],
        "finish": np.where(finish < INF * 0.99, finish, np.nan),
        "locality_rate": local / (local + remote) if local + remote else 0.0,
    }
