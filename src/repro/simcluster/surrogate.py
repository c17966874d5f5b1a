"""Batched fluid surrogate of the event engine (``lax.scan`` × ``vmap``).

The event simulator (``repro.simcluster.sim``) prices every heartbeat,
launch and finish as a discrete event — exact, but one Python process per
cell.  This module trades task-level exactness for three orders of
magnitude in throughput: each cell (trace × policy × seed) becomes a
fixed-timestep **fluid** model whose state is arrays over jobs — pending
map/reduce task mass, slot allocations, locality fractions, latch state —
advanced with ``lax.scan`` over time and ``jax.vmap`` over cells, so
thousands of cells integrate in one XLA computation.

What is modeled (the mesoscale):

* slot capacity (``num_nodes × base_map_slots`` map, same for reduce) and
  per-step allocation by policy ordering — EDF (static deadline priority),
  FIFO (static submission priority), fair deficit (equal-share
  waterfilling);
* the map→reduce phase barrier (reduces only after the job's map mass
  drains, as Algorithm 2 line 10);
* data locality as a hit probability: a free slot finds a local block with
  ``1 − (1 − c/N)^p`` for ``p`` pending tasks whose blocks each live on
  ``c`` distinct nodes of ``N`` — wide backlogs run local, job tails go
  remote, which is the entire economics of delay scheduling and parking;
* the paper's parking mechanism (``park: fixed``) as a conversion of the
  non-local flow into local launches that pay a reconfiguration wait
  instead of the remote-read penalty;
* delay scheduling (``locality_delay``) as an exponent boost on the
  locality hit probability;
* the latching overload detector (``overload: latch``): when the queued
  map backlog and the active-job crowd cross the ``AdaptiveConfig`` entry
  bars, ordering degenerates to fair and parking suspends until the
  cluster drains.

What is **not** modeled — and raises ``SurrogateUnsupported`` instead of
silently answering wrong: the pressure-adaptive park gates (``park:
adaptive`` — donor-interval EWMAs, fail streaks, win-rate floors) and the
reduce-aware latch (``overload: reduce_aware``).  Those live on event-level
signals (per-machine donor timing) with no fluid equivalent; the policies
``adaptive`` and ``adaptive_ra`` stay oracle-only.

Determinism contract, pinned on CPU by ``tests/test_surrogate.py`` and on
one TPU v5e by ``chip_smoke.py`` (80 fleet cells at 200x2): per (config,
seed) the result is byte-stable on a platform; a batch of one through
``vmap`` is bit-identical to the unbatched kernel; and a cell's result is
invariant to the batch it rides in and to the sub-batch cap — padding
buckets (``_bucket``) are a function of the cell alone, never of its batch
mates.  Across platforms the result is not bitwise: on the v5e the
launch-mass sums (hence locality) differ from the CPU's by ~3e-7 while
finish times, finished jobs and met deadlines match, so the sweep cache
keys surrogate cells on the platform off the CPU.
"""
from __future__ import annotations

import math
import os
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.policies import PolicySpec
from repro.core.tracing import span
from repro.core.types import AdaptiveConfig, ClusterSpec
from repro.simcluster.traces import Trace, _stable_seed
from repro.simcluster.workloads import (WORKLOADS, distinct_holders,
                                        n_map_tasks, task_counts)

#: engine identity stamped into cache descriptors and bench entries.  The
#: event engine's cells carry no ``engine`` key at all, so every surrogate
#: hash lands in a disjoint namespace (see tests/test_experiments.py).
SURROGATE_ENGINE_ID = "simcluster.surrogate/fluid-v1"

#: component vocabulary the lowering can express.  Everything else is
#: oracle-only and raises ``SurrogateUnsupported``.
SUPPORTED_COMPONENTS: Dict[str, Tuple[str, ...]] = {
    "ordering": ("edf", "fair_deficit", "fifo"),
    "park": ("off", "fixed"),
    "overload": ("none", "latch"),
}

_ORDERING_CODES = {"edf": 0, "fifo": 1, "fair_deficit": 2}

# -- fluid-model calibration constants ---------------------------------------
# Fitted against paired event-engine cells on the regime atlas (the
# differential wall in tests/test_surrogate.py re-checks the fit on every
# run); they are physics of the mesoscale model, not per-preset knobs.
#: integrator step, seconds of simulated time (2× the heartbeat interval:
#: fine enough that a 20 s map task spans >3 steps, coarse enough that a
#: 3600 s trace is ~600 steps)
DT = 6.0
#: fraction of parked (non-local) map candidates whose reconfiguration
#: resolves locally before the patience bound expires, on an uncrowded
#: cluster; crowding degrades it (see the crowd coupling below)
PARK_SUCCESS = 1.0
#: mean extra seconds a successfully parked map waits for its donor core
#: on an uncrowded cluster (hotplug latency + offer queueing)
PARK_WAIT = 6.0
# crowd coupling — the mesoscale form of the event engine's measured
# park economics: with many active jobs per machine, per-job shares sit
# far below job widths, donor offers queue behind stale ones, waits
# stretch toward the 30 s patience and expired parks still pay the
# remote read afterwards.  χ = clip(active_jobs / machines, 0, 1):
#: park win probability shrinks as (1 − slope × χ)
PARK_CROWD_PENALTY = 1.0
#: successful-park wait grows to PARK_WAIT × (1 + slope × χ)
PARK_WAIT_CROWD = 0.5
#: above χ ≈ 0.6 the donor pool is exhausted and expired parks re-park
#: (depth 2) before finally reading remote: the patience bound stretches
#: by up to this factor at full saturation — the regime that separates
#: synchronized-burst traces (which spike to χ = 1) from steady backlogs
REPARK_CROWD = 6.0
#: saturation ramp for the repark stretch: saturate = clip((χ_raw − SAT_LO)
#: / SAT_WIDTH, 0, 1) on the *uncapped* active/machines ratio, so only
#: backlogs that outrun the fleet (χ_raw → 1+) pay the full stretch
SAT_LO = 0.75
SAT_WIDTH = 0.3
#: effective placement draws per launch for the non-delay schedulers —
#: the event engine's offer scan finds a local-feasible task ~this many
#: times more often than a single uniform draw would (fair and fifo both
#: measure ~0.2 locality against a 1/machines ~ 0.05 uniform baseline)
LOCALITY_DRAWS = 8.0
#: delay scheduling: extra locality draws per skipped offer (multiplies
#: the hit-probability exponent by 1 + boost × locality_delay)
DELAY_BOOST = 0.35
#: delay scheduling's price: a task that gives up and goes remote first
#: sat out its full skip budget — its launch pays an extra
#: ``locality_delay × DELAY_REMOTE_WAIT`` seconds of ring lag
DELAY_REMOTE_WAIT = 2.0
#: fabric contention: remote map reads this step slow each other down by
#: 1 + slope × (remote launch mass / map slots) — a priority wave that
#: sends most of the queue remote at once pays more per read than fair's
#: trickle of the same total remote mass
NET_CONTENTION = 1.25
#: mean task-duration inflation from the straggler process net of
#: speculative re-execution (p × (factor−1), roughly halved by speculation)
TAIL_INFLATION = 1.04
#: waterfilling iterations for the fair-share allocator (exact once the
#: distinct binding demand levels are below this; J ≤ 64 needs few)
_FAIR_ITERS = 8
#: in-flight ring depth, steps: launched tasks occupy their slots for
#: their quantized service time via a (jobs × _RING) delay ring; service
#: lags clip to _RING − 1 (= 378 s at DT, far above any per-task time)
_RING = 64
#: steps per early-exit chunk: the kernel integrates whole chunks and
#: counts them per cell (its ``chunks`` output); step buckets are whole
#: chunks
CHUNK = 256
_EPS = 1e-6
_INF = np.float32(3.0e9)


class SurrogateUnsupported(ValueError):
    """A policy contains a component the fluid surrogate cannot model.

    Carries the offending axis/value so callers can report *why* a policy
    is oracle-only rather than silently approximating it."""

    def __init__(self, label: str, axis: str, value: str):
        self.label = label
        self.axis = axis
        self.value = value
        super().__init__(
            f"policy {label!r} is oracle-only: component {axis}={value!r} "
            f"has no surrogate transition (supported: "
            f"{SUPPORTED_COMPONENTS.get(axis, ())})")


@dataclass(frozen=True)
class LoweredPolicy:
    """A ``PolicySpec`` compiled to the surrogate's scalar program."""

    ordering: int          # _ORDERING_CODES
    park: int              # 0 = off, 1 = fixed
    overload: int          # 0 = none, 1 = latch
    locality_delay: float  # delay-scheduling offers (fair-family only)
    max_wait: float        # park patience bound, seconds (park policies)


def lower_policy(policy) -> LoweredPolicy:
    """Lower a policy value (spec / name / dict / JSON) to the surrogate
    program, or raise :class:`SurrogateUnsupported` — never a silent
    approximation of an unmodeled component."""
    spec = PolicySpec.parse(policy)
    comps = spec.components
    for axis in ("ordering", "park", "overload"):
        value = comps.get(axis)
        if value not in SUPPORTED_COMPONENTS[axis]:
            raise SurrogateUnsupported(spec.label, axis, str(value))
    params = spec.effective_params()
    park = 1 if comps["park"] == "fixed" else 0
    return LoweredPolicy(
        ordering=_ORDERING_CODES[comps["ordering"]],
        park=park,
        overload=1 if comps["overload"] == "latch" else 0,
        locality_delay=float(params.get("locality_delay", 0) or 0),
        max_wait=float(params.get("max_wait", 30.0)) if park else 0.0)


def surrogate_supported(policy) -> bool:
    """True when :func:`lower_policy` would accept this policy."""
    try:
        lower_policy(policy)
        return True
    except SurrogateUnsupported:
        return False


# ---------------------------------------------------------------------------
# cell construction (host side, numpy)
# ---------------------------------------------------------------------------

def _bucket(n: int, base: int) -> int:
    """Smallest ``base × 2^k`` ≥ n — a deterministic function of the cell
    alone, so padded shapes (and therefore results) cannot depend on what
    else shares the batch."""
    size = base
    while size < n:
        size *= 2
    return size


@dataclass
class SurrogateCellInputs:
    """One cell's arrays, unpadded (jobs axis = J), plus static scalars."""

    # per-job arrays, float32/np
    submit: np.ndarray          # absolute submit time
    dl_abs: np.ndarray          # absolute deadline
    u_m: np.ndarray             # map tasks
    v_r: np.ndarray             # reduce tasks
    map_t: np.ndarray           # mean local map-task seconds (jittered)
    red_t: np.ndarray           # mean reduce-task seconds (jittered)
    c_repl: np.ndarray          # mean distinct replica nodes per map block
    # cell scalars
    n_nodes: int
    n_machines: int
    map_slots: float
    red_slots: float
    remote_mult: float          # remote map duration multiplier
    policy: LoweredPolicy
    # latch entry bars (AdaptiveConfig defaults unless the cluster overrides)
    overload_pending_factor: float
    overload_active_factor: float
    horizon: float
    job_ids: List[str]
    workloads: List[str]
    input_gb: List[float]
    deadlines_rel: np.ndarray

    @property
    def n_jobs(self) -> int:
        return int(self.submit.shape[0])

    def padded_jobs(self) -> int:
        return _bucket(self.n_jobs, 8)

    def n_steps(self) -> int:
        return _bucket(int(math.ceil(self.horizon / DT)), CHUNK)


def build_cell(trace: Trace, cluster: ClusterSpec, policy,
               seed: int) -> SurrogateCellInputs:
    """Compile one (trace, cluster, policy) cell to surrogate inputs.

    Uses the *actual* trace jobs — submit times, task counts, profiles,
    deadlines and the replica count HDFS placement gives each block — so
    the surrogate shares every input the event engine sees and
    approximates only the dynamics.  ``seed`` additionally drives a small
    per-job duration jitter standing in for the event engine's per-task
    lognormal draw."""
    lowered = lower_policy(policy)
    jobs = trace.jobs
    # rows straight from the trace: the fluid model reads a job's blocks
    # only through their distinct holders, a constant of the cluster
    # (``distinct_holders``), so no block is placed
    with span("repro.surrogate.job_specs",
              blocks=sum(n_map_tasks(j.input_gb) for j in jobs)):
        profiles = [WORKLOADS[j.workload] for j in jobs]
        counts = [task_counts(j.workload, j.input_gb) for j in jobs]
    n = len(jobs)
    if n == 0:
        raise ValueError("surrogate cell needs at least one job")
    rng = np.random.default_rng(
        _stable_seed("surrogate-jitter", trace.name, trace.seed, seed))
    submit = np.array([j.submit_time for j in jobs], np.float32)
    dl_rel = np.array([j.deadline for j in jobs], np.float32)
    u_m = np.array([um for um, _ in counts], np.float32)
    v_r = np.array([vr for _, vr in counts], np.float32)
    # per-job mean durations; the phase mean over u_m iid task draws
    # concentrates ∝ 1/sqrt(u_m), which the jitter std reproduces
    map_t = np.empty(n, np.float32)
    red_t = np.empty(n, np.float32)
    for i, (prof, (um, vr)) in enumerate(zip(profiles, counts)):
        cv = getattr(prof, "time_cv", 0.08)
        z_m, z_r = rng.standard_normal(2)
        jitter_m = math.exp(cv * z_m / math.sqrt(max(um, 1)))
        jitter_r = math.exp(cv * z_r / math.sqrt(max(vr, 1)))
        map_t[i] = prof.map_time * TAIL_INFLATION * jitter_m
        red_t[i] = ((prof.reduce_time + um * prof.shuffle_time_per_pair)
                    * TAIL_INFLATION * jitter_r)
    c_repl = np.full(n, float(distinct_holders(cluster)), np.float32)
    # remote penalty is profile-uniform today (1.0); keep the first job's
    # profile as the cell's fabric calibration like the event engine does
    rp = profiles[0].remote_penalty
    remote_mult = 1.0 + rp * cluster.remote_penalty_scale
    map_slots = float(cluster.num_nodes * cluster.base_map_slots)
    red_slots = float(cluster.num_nodes * cluster.base_reduce_slots)
    total_work = (float(np.sum(u_m * map_t)) * remote_mult / map_slots
                  + float(np.sum(v_r * red_t)) / red_slots)
    horizon = float(np.max(submit)) + 3.0 * total_work + 900.0
    adaptive = cluster.adaptive if isinstance(cluster.adaptive,
                                              AdaptiveConfig) else AdaptiveConfig()
    return SurrogateCellInputs(
        submit=submit, dl_abs=submit + dl_rel, u_m=u_m, v_r=v_r,
        map_t=map_t, red_t=red_t, c_repl=c_repl,
        n_nodes=cluster.num_nodes, n_machines=cluster.num_machines,
        map_slots=map_slots, red_slots=red_slots, remote_mult=remote_mult,
        policy=lowered,
        overload_pending_factor=adaptive.overload_pending_factor,
        overload_active_factor=adaptive.overload_active_factor,
        horizon=horizon,
        job_ids=[j.job_id for j in jobs],
        workloads=[p.name for p in profiles],
        input_gb=[j.input_gb for j in jobs],
        deadlines_rel=dl_rel)


# ---------------------------------------------------------------------------
# the kernel: lax.scan over time, vmap over cells
# ---------------------------------------------------------------------------

#: names and order of the per-job tensor rows handed to the kernel
_JOB_FIELDS = ("submit", "dl_abs", "map_mass0", "red_mass0", "lag_ml",
               "lag_mr", "lag_rr", "c_over_n", "pad_mask")
#: per-cell scalar rows
_SCALAR_FIELDS = ("map_slots", "red_slots", "machines", "remote_mult",
                  "ordering", "park", "overload", "locality_delay",
                  "max_wait", "pending_bar", "active_bar")


def priority_order(cell: SurrogateCellInputs) -> np.ndarray:
    """The cell's jobs in strict-priority order: FIFO by submission, every
    other ordering by absolute deadline (fair ignores it).  The sort is
    stable, so ties keep job index order, the event schedulers'
    admission-seq tiebreak."""
    if cell.policy.ordering == _ORDERING_CODES["fifo"]:
        key = cell.submit
    else:
        key = cell.dl_abs
    return np.argsort(key, kind="stable")


def pack_cell(cell: SurrogateCellInputs) -> Dict[str, np.ndarray]:
    """Pad one cell's arrays to its job bucket and stack the kernel inputs,
    jobs in :func:`priority_order`.  Padding jobs sit last, carry zero
    mass and a pad mask of 0 — they can never activate, allocate, or
    finish."""
    jp = cell.padded_jobs()
    n = cell.n_jobs
    order = priority_order(cell)

    def pad(a: np.ndarray, fill: float = 0.0) -> np.ndarray:
        out = np.full(jp, fill, np.float32)
        out[:n] = a[order].astype(np.float32)
        return out

    pol = cell.policy

    def lag(seconds: np.ndarray) -> np.ndarray:
        return np.clip(np.round(seconds / DT), 1, _RING - 1)

    jobs = {
        "submit": pad(cell.submit, fill=_INF),
        "dl_abs": pad(cell.dl_abs, fill=_INF),
        "map_mass0": pad(cell.u_m),
        "red_mass0": pad(cell.v_r),
        "lag_ml": pad(lag(cell.map_t), fill=1.0),
        "lag_mr": pad(lag(cell.map_t * cell.remote_mult), fill=1.0),
        "lag_rr": pad(lag(cell.red_t), fill=1.0),
        "c_over_n": pad(np.minimum(cell.c_repl / cell.n_nodes, 0.999)),
        "pad_mask": pad(np.ones(n, np.float32)),
    }
    scalars = {
        "map_slots": cell.map_slots,
        "red_slots": cell.red_slots,
        "machines": float(cell.n_machines),
        "remote_mult": cell.remote_mult,
        "ordering": float(pol.ordering),
        "park": float(pol.park),
        "overload": float(pol.overload),
        "locality_delay": pol.locality_delay,
        "max_wait": pol.max_wait,
        "pending_bar": cell.overload_pending_factor * cell.map_slots,
        "active_bar": cell.overload_active_factor * cell.n_machines,
    }
    packed = {k: jobs[k] for k in _JOB_FIELDS}
    packed.update({k: np.float32(scalars[k]) for k in _SCALAR_FIELDS})
    return packed


def _fair_waterfill(jnp, demand, capacity):
    """Equal-share progressive filling of ``capacity`` over ``demand``
    (deficit round-robin's fluid limit).  Unrolled fixed-point: each round
    splits the leftover equally among unsatisfied jobs."""
    alloc = jnp.zeros_like(demand)
    for _ in range(_FAIR_ITERS):
        need = demand - alloc
        unsat = (need > _EPS).astype(demand.dtype)
        n_unsat = jnp.maximum(jnp.sum(unsat), 1.0)
        leftover = jnp.maximum(capacity - jnp.sum(alloc), 0.0)
        share = leftover / n_unsat
        alloc = alloc + jnp.minimum(need, share) * unsat
    return alloc


def _priority_alloc(jnp, demand, capacity):
    """Strict-priority waterfilling: jobs take their full demand in the
    order they lie in (:func:`pack_cell` packs them in priority order)
    until capacity runs out."""
    before = jnp.cumsum(demand) - demand
    return jnp.clip(capacity - before, 0.0, demand)


def _make_kernel(n_jobs: int, n_steps: int, diag: bool = False):
    """Build the single-cell scan kernel for a (jobs, steps) bucket.

    The dynamics are a *discrete-lag fluid*: pending task mass launches
    into free slots and sits in a (jobs × ``_RING``) in-flight delay ring
    for its quantized service time before completing — so waves, slot
    occupancy, queueing and the map→reduce barrier are all emergent, with
    no closed-form drain law to mis-calibrate.  A launch's service lag is
    its locality outcome (local / remote / parked), so locality economics
    feed straight into capacity.

    Jobs arrive in priority order (:func:`pack_cell`), so strict-priority
    allocation fills them as they lie, and outputs come back in that order.

    Returns ``kernel(packed) -> outputs`` where outputs are per-job
    ``finish`` times (``_INF`` = unfinished), accumulated local/remote
    launch mass, the latched-step count, and ``chunks``: the int32 count
    of ``CHUNK``-step chunks the early-exit loop ran (``n_steps // CHUNK``
    on the ``diag`` scan).  Each stage of a step runs under a
    ``jax.named_scope`` (``KERNEL_STAGES``), which names its operations in
    the compiled executable (:func:`kernel_stages`).  ``diag=True``
    additionally stacks per-step cluster aggregates (active jobs, queued
    mass, free slots, launch totals, launch-weighted locality, crowding,
    latch) — the observability hook calibration probes use.  Pure jnp:
    safe under both direct call and ``vmap``."""
    import jax
    import jax.numpy as jnp

    dt = np.float32(DT)
    L = _RING

    def kernel(p):
        with jax.named_scope("setup"):
            submit = p["submit"]
            pad_mask = p["pad_mask"]
            lag_ml = p["lag_ml"].astype(jnp.int32)
            lag_mr = p["lag_mr"].astype(jnp.int32)
            lag_rr = p["lag_rr"].astype(jnp.int32)
            log_miss = jnp.log1p(-p["c_over_n"])       # per-job, < 0
            use_fair_ordering = p["ordering"] >= 1.5   # fair_deficit code
            # delay scheduling: each skipped offer is more locality draws
            ell_exponent = 1.0 + DELAY_BOOST * p["locality_delay"]

        def step(carry, it):
            (pend_m, ring_m, pend_r, ring_r, park_s, park_x, finish,
             loc_acc, rem_acc, latch, lsteps) = carry
            # completions leave the ring first — they free slots this
            # step.  Ring maintenance is O(J) scatter/gather on the
            # maturing column; each ring pays exactly one full O(J·L)
            # reduction per step and every later sum is derived from it
            # arithmetically (the scan spends its time in these rows).
            with jax.named_scope("ring_drain"):
                idx = jnp.mod(it, L)
                ring_m = ring_m.at[:, idx].set(0.0)
                ring_r = ring_r.at[:, idx].set(0.0)
                # parked mass whose wait matures this step enters
                # service: a successful park runs local, an expired one
                # reads remote
                mat_s = park_s[:, idx]
                mat_x = park_x[:, idx]
                park_s = park_s.at[:, idx].set(0.0)
                park_x = park_x.at[:, idx].set(0.0)
                inflight_m = jnp.sum(ring_m, axis=1)
                inflight_r = jnp.sum(ring_r, axis=1)
                waiting = jnp.sum(park_s, axis=1) + jnp.sum(park_x, axis=1)
            # latch entry/exit on beginning-of-step queue pressure
            with jax.named_scope("latch"):
                t = it.astype(jnp.float32) * dt
                submitted = (submit <= t).astype(jnp.float32) * pad_mask
                map_left = pend_m + inflight_m + waiting + mat_s + mat_x
                red_left = pend_r + inflight_r
                map_open = submitted * (map_left > _EPS)
                red_open = submitted * (map_left <= _EPS) * (red_left > _EPS)
                pending = jnp.sum(pend_m * submitted)
                active = jnp.sum(submitted * ((map_left > _EPS)
                                              | (red_left > _EPS)))
                trip = ((pending >= p["pending_bar"])
                        & (active >= p["active_bar"]))
                latch = (p["overload"] > 0.5) & ((latch | trip)
                                                 & (active > 0.5))
                use_fair = use_fair_ordering | latch
                park_on = (p["park"] > 0.5) & ~latch
                chi_raw = active / p["machines"]
                chi = jnp.clip(chi_raw, 0.0, 1.0)
            # -- map demand ----------------------------------------------
            # a parked task donates its core to the reconfiguration pool,
            # where it is *held* for the donor wait — unavailable to the
            # scheduler.  That capacity holdback is the park tax the
            # oracle measures (diurnal proposed runs the map pool at
            # ~50% utilization through its overload phase).
            with jax.named_scope("map_alloc"):
                free_m = jnp.maximum(
                    p["map_slots"] - jnp.sum(inflight_m) - jnp.sum(waiting),
                    0.0)
                # two allocation rounds, after the event scheduler's
                # demand/backfill phases: round 1 caps each job at its
                # share of the pool (parked tasks count as in-flight
                # against it), round 2 backfills leftover slots with any
                # uncapped pending mass — so a heavy-tailed giant keeps
                # freed slots busy, while a fleet of similar jobs that all
                # parked together has nothing left to backfill with and
                # the pool idles.
                n_open = jnp.maximum(jnp.sum(map_open), 1.0)
                share = p["map_slots"] / n_open
                cap = jnp.maximum(share - waiting, 0.0)
                offered = jnp.minimum(pend_m, cap) * map_open
                launch1 = jnp.where(
                    use_fair,
                    _fair_waterfill(jnp, offered, free_m),
                    _priority_alloc(jnp, offered, free_m))
                spare = jnp.maximum(free_m - jnp.sum(launch1), 0.0)
                off2 = jnp.maximum(pend_m - launch1, 0.0) * map_open
                launch2 = jnp.where(
                    use_fair,
                    _fair_waterfill(jnp, off2, spare),
                    _priority_alloc(jnp, off2, spare))
                launch = launch1 + launch2
                blocked = jnp.sum(waiting)
                pend_m = jnp.maximum(pend_m - launch, 0.0)
                pend_m = jnp.where(pend_m <= 0.01, 0.0, pend_m)
            with jax.named_scope("park"):
                # baseline locality: the offer scan's effective placement
                # draws per launch (constant — the event engine books
                # ~the same locality for fair and fifo); delay
                # scheduling's skipped offers multiply the draws
                lf_base = 1.0 - jnp.exp(ell_exponent * LOCALITY_DRAWS
                                        * log_miss)
                launch_loc = launch * lf_base
                rest = launch - launch_loc
                # park outcome odds and waits, degraded by the active
                # crowd (donor cores are co-located VMs' spare capacity)
                wait_eff = jnp.minimum(
                    PARK_WAIT * (1.0 + PARK_WAIT_CROWD * chi), p["max_wait"])
                p_succ = PARK_SUCCESS * jnp.maximum(
                    1.0 - PARK_CROWD_PENALTY * chi, 0.0)
                ws = jnp.round(wait_eff / dt).astype(jnp.int32)
                saturate = jnp.clip((chi_raw - SAT_LO) / SAT_WIDTH, 0.0, 1.0)
                wx = jnp.minimum(jnp.round(
                    p["max_wait"] * (1.0 + REPARK_CROWD * saturate) / dt
                ).astype(jnp.int32), L - 1)
                # deadline-critical bypass (the event reconfigurator's own
                # guard, verbatim): a job inside 3x the park patience of
                # its absolute deadline skips parking and reads remote
                # immediately — so a blown-deadline backlog stops donating
                # its launches to the park queue.
                crit = (p["dl_abs"] - t) <= 3.0 * p["max_wait"]
                park_f = park_on.astype(jnp.float32) \
                    * (1.0 - crit.astype(jnp.float32))
                f_psucc = rest * park_f * p_succ
                f_pexp = rest * park_f * (1.0 - p_succ)
                f_rem = rest * (1.0 - park_f)
                # remote reads launched together contend on the fabric
                rem_load = jnp.sum(f_rem + mat_x) / p["map_slots"]
                delay_lag = jnp.round(
                    DELAY_REMOTE_WAIT * p["locality_delay"] / dt
                ).astype(jnp.int32)
                lag_mr_eff = jnp.minimum(
                    lag_mr + delay_lag + jnp.round(
                        lag_mr.astype(jnp.float32) * NET_CONTENTION
                        * rem_load).astype(jnp.int32), L - 1)
                loc_acc = loc_acc + launch_loc + f_psucc
                rem_acc = rem_acc + f_rem + f_pexp
                lf = (launch_loc + f_psucc) / jnp.maximum(launch, _EPS)
            rows = jnp.arange(n_jobs)
            with jax.named_scope("ring_scatter"):
                ring_m = ring_m.at[rows, jnp.mod(it + lag_ml, L)].add(
                    launch_loc + mat_s)
                ring_m = ring_m.at[rows, jnp.mod(it + lag_mr_eff, L)].add(
                    f_rem + mat_x)
                park_s = park_s.at[:, jnp.mod(it + ws, L)].add(f_psucc)
                park_x = park_x.at[:, jnp.mod(it + wx, L)].add(f_pexp)
            # -- reduce --------------------------------------------------
            with jax.named_scope("reduce"):
                off_r = pend_r * red_open
                free_r = jnp.maximum(p["red_slots"] - jnp.sum(inflight_r),
                                     0.0)
                launch_r = jnp.where(
                    use_fair,
                    _fair_waterfill(jnp, off_r, free_r),
                    _priority_alloc(jnp, off_r, free_r))
                # the reduce ring's scatter is ring maintenance too
                with jax.named_scope("ring_scatter"):
                    ring_r = ring_r.at[rows, jnp.mod(it + lag_rr, L)].add(
                        launch_r)
                pend_r = jnp.maximum(pend_r - launch_r, 0.0)
                pend_r = jnp.where(pend_r <= 0.01, 0.0, pend_r)
            # -- completions ---------------------------------------------
            # post-launch remaining mass, derived from the pre-launch
            # reductions plus exactly what this step scattered in
            with jax.named_scope("completion"):
                map_left = pend_m + inflight_m + launch_loc + mat_s \
                    + f_rem + mat_x + waiting + f_psucc + f_pexp
                red_left = pend_r + inflight_r + launch_r
                done = (submitted > 0.5) & (map_left <= _EPS) \
                    & (red_left <= _EPS)
                finish = jnp.where(done & (finish >= _INF), t + dt, finish)
                lsteps = lsteps + latch.astype(jnp.float32)
            ys = None
            if diag:
                lsum = jnp.maximum(jnp.sum(launch), _EPS)
                ys = {"active": active, "pending": pending,
                      "free_m": free_m, "free_r": free_r,
                      "waiting": jnp.sum(waiting), "blocked": blocked,
                      "launched_m": jnp.sum(launch),
                      "launched_r": jnp.sum(launch_r),
                      "lf": jnp.sum(lf * launch) / lsum,
                      "chi": chi,
                      "latch": latch.astype(jnp.float32)}
            return (pend_m, ring_m, pend_r, ring_r, park_s, park_x,
                    finish, loc_acc, rem_acc, latch, lsteps), ys

        init = (p["map_mass0"],
                jnp.zeros((n_jobs, L), jnp.float32),
                p["red_mass0"],
                jnp.zeros((n_jobs, L), jnp.float32),
                jnp.zeros((n_jobs, L), jnp.float32),
                jnp.zeros((n_jobs, L), jnp.float32),
                jnp.full((n_jobs,), _INF, jnp.float32),
                jnp.zeros((n_jobs,), jnp.float32),
                jnp.zeros((n_jobs,), jnp.float32),
                jnp.asarray(False),
                jnp.asarray(0.0, jnp.float32))
        if diag:
            its = jnp.arange(n_steps, dtype=jnp.int32)
            final, ys = jax.lax.scan(step, init, its)
            chunks = jnp.asarray(n_steps // CHUNK, jnp.int32)
        else:
            # early exit at chunk granularity: once every real job has
            # finished, further steps are exact no-ops (no pending mass,
            # empty rings, latch released), so skipping them is
            # bit-identical to integrating the full horizon — the scan
            # just stops paying for the drain tail.
            n_chunks = max(n_steps // CHUNK, 1)

            def unfinished(carry):
                with jax.named_scope("early_exit"):
                    return jnp.any((carry[6] >= _INF) & (pad_mask > 0.5))

            def cond(state):
                carry, c = state
                return (c < n_chunks) & unfinished(carry)

            def body(state):
                carry, c = state
                its = c * CHUNK + jnp.arange(CHUNK, dtype=jnp.int32)
                carry, _ = jax.lax.scan(step, carry, its)
                return (carry, c + 1)

            # under vmap each lane keeps its own count: a finished lane's
            # carry, counter included, stops changing
            final, chunks = jax.lax.while_loop(
                cond, body, (init, jnp.asarray(0, jnp.int32)))
            ys = None
        (pend_m, _, pend_r, _, _, _, finish, loc_acc, rem_acc, _,
         lsteps) = final
        out = {"finish": finish, "local": loc_acc, "remote": rem_acc,
               "map_rem": pend_m, "red_rem": pend_r,
               "latched_steps": lsteps, "chunks": chunks}
        if diag:
            out["diag"] = ys
        return out

    return kernel


_KERNEL_CACHE: Dict[Tuple[int, int, bool, bool], object] = {}

#: the checkout root (``src/repro/simcluster/`` → three levels up)
_CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Call from an entry point before the first compile, never at import.
    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    this sets no other directory.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache/``: the directory is part of what a later
    process must find again, so it never takes a temporary name."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(_CHECKOUT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


#: cells per vmapped sub-batch in run_batch.  On the CPU it is large enough
#: to amortize dispatch, small enough that the scan carry stays
#: cache-resident.  On one TPU v5e the per-cell cost is nearly flat in the
#: cap: 80 fleet cells (1024 jobs x 4096 steps) took 14.4 s at 64, 13.4 s
#: at 16 and 18.6 s at 1, so the device's per-step work grows with the
#: batch and the cap mostly decides how many shapes compile (each
#: sub-batch size, remainder included, is its own compile).
#: Overridable per-call (``run_batch(..., max_batch=...)``) or process-wide
#: via ``REPRO_SURROGATE_MAX_BATCH``; per-cell results are bit-identical
#: at every cap, on the CPU and on the chip.
_MAX_BATCH = 64


def _resolve_max_batch(max_batch: Optional[int] = None) -> int:
    """Sub-batch cap for ``run_batch``: explicit kwarg beats the
    ``REPRO_SURROGATE_MAX_BATCH`` env var beats the built-in default."""
    if max_batch is None:
        env = os.environ.get("REPRO_SURROGATE_MAX_BATCH")
        if env:
            max_batch = int(env)
        else:
            return _MAX_BATCH
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    return max_batch


def _compiled(n_jobs: int, n_steps: int, batched: bool, diag: bool = False):
    """jit-compiled kernel per (bucket, batched) — the cache keeps repeat
    sweeps from re-tracing."""
    import jax
    key = (n_jobs, n_steps, batched, diag)
    fn = _KERNEL_CACHE.get(key)
    if fn is None:
        kernel = _make_kernel(n_jobs, n_steps, diag=diag)
        fn = jax.jit(jax.vmap(kernel) if batched else kernel)
        _KERNEL_CACHE[key] = fn
    return fn


#: the kernel's ``jax.named_scope`` stages: the prologue, the early exit's
#: test, then a step's stages in order
KERNEL_STAGES = ("setup", "early_exit", "ring_drain", "latch", "map_alloc",
                 "park", "ring_scatter", "reduce", "completion")
#: the stage of an operation whose ``op_name`` names none
UNSCOPED = "unscoped"
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_HLO_OP = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+) = (\w+\[([\d,]*)\])?.*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_NAMES = re.compile(r"%([\w.\-]+)")
#: control flow: its own time is loop plumbing, whatever its body holds
_CONTROL = ("while", "conditional", "call")


def _stage_of(op_name: str) -> str:
    """The innermost stage in an ``op_name`` path.  Its last component is
    the primitive, never a scope; a scope can show as ``vmap(<scope>)``."""
    for part in reversed(op_name.split("/")[:-1]):
        for token in reversed(re.split(r"[()]", part)):
            if token in KERNEL_STAGES:
                return token
    return UNSCOPED


@dataclass
class _HloOp:
    own: str                 # the stage its own ``op_name`` names
    opcode: str
    size: int                # elements of an array result, 0 for a tuple
    called: List[str]        # computations it calls (fusion, to_apply)
    operands: List[str]


def hlo_stages(text: str) -> Dict[str, str]:
    """``{instruction name: stage}`` of every instruction in an HLO text.

    A fusion takes the stage of its fused computation's root (else of its
    first instruction that has one); where that root is a tuple (a
    multi-output fusion, which can join stages), the stage of its largest
    element.  Any other instruction takes the
    innermost stage its own ``op_name`` names.  XLA makes some
    instructions without metadata (layout copies, a scatter it rewrites,
    the windows of a decomposed cumsum); these take the stage of the
    computation they call, else of their first operand that has one.
    Control flow, and what has none of these, stays ``UNSCOPED``."""
    ops: Dict[str, _HloOp] = {}
    comps: Dict[str, List[str]] = {}      # computation -> [root, *members]
    members: List[str] = [""]
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head and not line[0].isspace():
            members = comps.setdefault(head.group(1), [""])
            continue
        op = _HLO_OP.match(line)
        if op is None:
            continue
        name = op.group(2)
        meta = _OP_NAME.search(line)
        called = _CALLS.findall(line)
        dims = op.group(4)
        ops[name] = _HloOp(
            own=_stage_of(meta.group(1)) if meta else UNSCOPED,
            opcode=op.group(5),
            size=0 if op.group(3) is None else math.prod(
                int(d) for d in dims.split(",") if d),
            called=called,
            operands=[n for n in _NAMES.findall(line.split(" = ", 1)[1])
                      if n not in called])
        members.append(name)
        if op.group(1):
            members[0] = name
    stages: Dict[str, str] = {}

    def of_computation(comp: str) -> str:
        root, *body = comps.get(comp, [""])
        if root and ops[root].opcode == "tuple":
            parts = sorted(ops[root].operands, key=lambda n: -ops[n].size)
        else:
            parts = [root] if root else []
        for part in parts + body:
            if stage(part) != UNSCOPED:
                return stages[part]
        return UNSCOPED

    def first(refs, of) -> str:
        for ref in refs:
            found = of(ref)
            if found != UNSCOPED:
                return found
        return UNSCOPED

    def stage(name: str) -> str:
        if name not in stages:
            stages[name] = UNSCOPED            # a cycle guard
            op = ops[name]
            found = first(op.called, of_computation) \
                if op.opcode == "fusion" else UNSCOPED
            if found == UNSCOPED:
                found = op.own
            if found == UNSCOPED and op.opcode not in _CONTROL:
                found = first(op.called, of_computation)
                if found == UNSCOPED:
                    found = first([r for r in op.operands if r in ops],
                                  stage)
            stages[name] = found
        return stages[name]

    for name in ops:
        stage(name)
    return stages


def kernel_stages(n_jobs: int, n_steps: int, lanes: int) -> Dict[str, str]:
    """``{HLO instruction name: stage}`` of the batched kernel's executable
    for ``lanes`` cells in the (``n_jobs``, ``n_steps``) bucket.

    Reads the compiled HLO text (:func:`hlo_stages`); a device trace names
    its operations by those instruction names, with or without the ``%``.
    The executable is the one ``run_batch`` runs, compiled through the
    same caches."""
    import jax
    shapes = {k: jax.ShapeDtypeStruct(
        (lanes, n_jobs) if k in _JOB_FIELDS else (lanes,), np.float32)
        for k in _JOB_FIELDS + _SCALAR_FIELDS}
    return hlo_stages(_compiled(n_jobs, n_steps, batched=True).lower(
        shapes).compile().as_text())


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

@dataclass
class SurrogateJob:
    job_id: str
    workload: str
    input_gb: float
    submit_time: float
    deadline: float              # relative
    finish_time: Optional[float]
    completion_time: Optional[float]
    deadline_met: bool
    local_map_launches: float
    remote_map_launches: float


@dataclass
class SurrogateResult:
    """Per-cell estimates, mirroring the ``SimResult`` metric surface the
    warehouse consumes (throughput/locality/deadlines)."""

    makespan: float
    jobs_total: int
    jobs_finished: int
    deadlines_met: int
    locality_rate: float
    latched_steps: float
    jobs: List[SurrogateJob]
    # per-step cluster aggregates, present when run with diag=True
    diag: Optional[Dict[str, np.ndarray]] = None

    def throughput_jobs_per_hour(self) -> float:
        if self.makespan <= 0:
            return 0.0
        return self.jobs_finished * 3600.0 / self.makespan


def _unpack_result(cell: SurrogateCellInputs, out: Dict[str, np.ndarray]
                   ) -> SurrogateResult:
    n = cell.n_jobs
    order = priority_order(cell)

    def in_trace_order(key: str) -> np.ndarray:
        # the kernel's rows are in priority order: put them back
        rows = np.empty(n, np.float64)
        rows[order] = out[key][:n]
        return rows

    finish = in_trace_order("finish")
    local = in_trace_order("local")
    remote = in_trace_order("remote")
    latched = float(np.asarray(out["latched_steps"]))
    finished = finish < float(_INF)
    jobs: List[SurrogateJob] = []
    deadlines = 0
    for i in range(n):
        ft = float(finish[i]) if finished[i] else None
        ct = None if ft is None else ft - float(cell.submit[i])
        met = ft is not None and ft <= float(cell.dl_abs[i]) + 1e-6
        deadlines += int(met)
        jobs.append(SurrogateJob(
            job_id=cell.job_ids[i], workload=cell.workloads[i],
            input_gb=float(cell.input_gb[i]),
            submit_time=float(cell.submit[i]),
            deadline=float(cell.deadlines_rel[i]),
            finish_time=ft, completion_time=ct, deadline_met=met,
            local_map_launches=float(local[i]),
            remote_map_launches=float(remote[i])))
    makespan = float(np.max(finish[finished])) if finished.any() \
        else cell.horizon
    launches = float(local.sum() + remote.sum())
    return SurrogateResult(
        makespan=makespan, jobs_total=n,
        jobs_finished=int(finished.sum()), deadlines_met=deadlines,
        locality_rate=float(local.sum()) / launches if launches else 0.0,
        latched_steps=latched, jobs=jobs)


def run_cell(cell: SurrogateCellInputs,
             diag: bool = False) -> SurrogateResult:
    """Integrate one cell through the *unbatched* kernel (the reference
    path the batch determinism tests compare against).  ``diag=True``
    attaches per-step cluster aggregates as ``result.diag`` (dict of
    time-series arrays) for calibration probes."""
    packed = pack_cell(cell)
    out = _compiled(cell.padded_jobs(), cell.n_steps(),
                    batched=False, diag=diag)(packed)
    traj = out.pop("diag", None)
    result = _unpack_result(cell,
                            {k: np.asarray(v) for k, v in out.items()})
    if traj is not None:
        result.diag = {k: np.asarray(v) for k, v in traj.items()}
    return result


def run_batch(cells: Sequence[SurrogateCellInputs], *,
              max_batch: Optional[int] = None) -> List[SurrogateResult]:
    """Integrate many cells, grouped by (jobs, steps) bucket and run
    through ``vmap`` in sub-batches of ``max_batch`` (default ``_MAX_BATCH``,
    overridable via ``REPRO_SURROGATE_MAX_BATCH``) — a handful of XLA
    computations for thousands of cells per call.  Results come back in
    input order and are bit-identical to ``run_cell`` on each cell alone,
    whatever the sub-batch cap (pinned by the fuzz suite)."""
    cap = _resolve_max_batch(max_batch)
    with span("repro.surrogate.run_batch"):
        groups: Dict[Tuple[int, int], List[int]] = {}
        for i, cell in enumerate(cells):
            groups.setdefault((cell.padded_jobs(), cell.n_steps()),
                              []).append(i)
        results: List[Optional[SurrogateResult]] = [None] * len(cells)
        for (jp, ts), idxs in groups.items():
            # sub-batch each bucket: per-cell results are independent of
            # batch composition (pinned by the fuzz suite), and moderate
            # batches keep the scan carry cache-resident — a single huge
            # vmap thrashes
            for lo in range(0, len(idxs), cap):
                part = idxs[lo:lo + cap]
                # lanes whose jobs the priority-order layout moves
                reordered = sum(
                    bool(np.any(priority_order(cells[i])
                                != np.arange(cells[i].n_jobs)))
                    for i in part)
                with span("repro.surrogate.pack", reordered=reordered):
                    packed = [pack_cell(cells[i]) for i in part]
                    stacked = {k: np.stack([q[k] for q in packed])
                               for k in packed[0]}
                # the call copies the inputs to the device and enqueues
                # the executable; a compile shows here, under its shape
                with span("repro.surrogate.dispatch", lanes=len(part),
                          jobs=jp, steps=ts):
                    out = _compiled(jp, ts, batched=True)(stacked)
                with span("repro.surrogate.fetch"):
                    out = {k: np.asarray(v) for k, v in out.items()}
                chunks = out["chunks"]
                with span("repro.surrogate.unpack", lanes=len(part),
                          jobs=jp, steps_run=int(chunks.max()) * CHUNK,
                          lane_steps_run=int(chunks.sum()) * CHUNK):
                    for row, i in enumerate(part):
                        results[i] = _unpack_result(
                            cells[i], {k: v[row] for k, v in out.items()})
        return results  # type: ignore[return-value]
