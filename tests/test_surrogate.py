"""Surrogate engine: the differential calibration wall that pins the fluid
model to the event oracle, plus property pins on the batched kernel.

The wall is the contract behind ``CALIBRATED``: for every allowlisted
(preset, shape, policy) the surrogate's policy-vs-fair throughput gain must
fall inside the event oracle's 95% paired-bootstrap CI on identical
(trace, seed) cells.  A preset enters the allowlist only by passing here —
and drifts out loudly, not silently, when either engine changes."""
import dataclasses
import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro.core.policies import PolicySpec, partition_policies
from repro.core.types import AdaptiveConfig, ClusterSpec
from repro.experiments.runner import ExperimentSpec, TraceRef
from repro.experiments.surrogate import (CALIBRATED, CALIBRATION_SEEDS,
                                         calibrate, run_surrogate,
                                         surrogate_descriptor,
                                         surrogate_hash)
from repro.simcluster.surrogate import (SURROGATE_ENGINE_ID,
                                        TAIL_INFLATION, SurrogateCellInputs,
                                        SurrogateUnsupported, build_cell,
                                        lower_policy, run_batch,
                                        surrogate_supported)
from repro.simcluster.traces import (PRESETS, _stable_seed, generate_trace,
                                     trace_from_rows)
from repro.simcluster.workloads import default_deadline

_CLUSTER = ClusterSpec(num_machines=6, vms_per_machine=2, replication=1)


def _cell(policy="proposed", seed=0, preset="mix_small", trace_seed=0,
          cluster=_CLUSTER):
    trace = generate_trace(PRESETS[preset], seed=trace_seed)
    return build_cell(trace, cluster, policy, seed)


def _fingerprint(res):
    """Every float the RunRecord surface consumes, exact — the comparison
    basis for all bit-identity pins below."""
    return (res.makespan, res.jobs_total, res.jobs_finished,
            res.deadlines_met, res.locality_rate, res.latched_steps,
            tuple((j.job_id, j.finish_time, j.completion_time,
                   j.deadline_met, j.local_map_launches,
                   j.remote_map_launches) for j in res.jobs))


# ---------------------------------------------------------------------------
# the differential calibration wall
# ---------------------------------------------------------------------------

def test_allowlist_is_pinned():
    """The calibrated set is a reviewed artifact: growing or shrinking it
    requires re-running the wall, not editing a dict."""
    assert CALIBRATED == {
        ("heavy_tail", "20x2"): ("proposed", "delay", "edf_nopark"),
        ("diurnal", "20x2"): ("proposed", "delay", "fifo", "edf_nopark"),
        ("bursty", "20x2"): ("fifo", "edf_nopark"),
        ("shuffle_heavy", "20x2"): ("delay", "fifo", "edf_nopark"),
        ("saturated", "20x2"): ("fifo", "edf_nopark"),
    }
    assert CALIBRATION_SEEDS == (0, 1, 2, 3)


@pytest.mark.parametrize("preset,shape", sorted(CALIBRATED))
def test_calibration_wall(preset, shape, tmp_path):
    """Surrogate + oracle on identical (trace, seed) cells; every
    allowlisted policy's surrogate gain inside the oracle's paired CI."""
    report = calibrate(preset, shape, tmp_path, workers=4)
    assert report.seeds == CALIBRATION_SEEDS
    assert {p.policy for p in report.policies} == set(
        CALIBRATED[(preset, shape)])
    for p in report.policies:
        assert p.allowlisted
        assert p.inside, (
            f"{preset}/{shape}/{p.policy}: surrogate gain "
            f"{p.surrogate_gain_pct:+.2f}% outside oracle CI "
            f"[{p.oracle.ci_lo_pct:+.2f}, {p.oracle.ci_hi_pct:+.2f}]")
    assert report.wall_green


def test_calibrate_extra_policy_not_allowlisted(tmp_path):
    """A policy under evaluation reports its differential without joining
    the gate: wall_green ignores non-allowlisted entries."""
    report = calibrate("heavy_tail", "20x2", tmp_path, seeds=(0,),
                       policies=("proposed", "fifo"), workers=4)
    flags = {p.policy: p.allowlisted for p in report.policies}
    assert flags == {"proposed": True, "fifo": False}


# ---------------------------------------------------------------------------
# sweep harness: cache behaviour and the lowering gate
# ---------------------------------------------------------------------------

def _small_spec(schedulers=("proposed", "fair"), seeds=(0, 1)):
    return ExperimentSpec(
        name="sur-t", traces=(TraceRef(preset="mix_small", seed=0),),
        clusters=(_CLUSTER,), schedulers=schedulers, seeds=seeds)


def test_surrogate_rerun_hits_cache(tmp_path):
    first = run_surrogate(_small_spec(), tmp_path)
    assert first.simulated == 4 and first.cached == 0
    again = run_surrogate(_small_spec(), tmp_path)
    assert again.simulated == 0 and again.cached == 4
    strip = lambda r: {k: v for k, v in r.to_dict().items()
                       if k != "wall_time_s"}
    assert [strip(r) for r in first.records] == \
        [strip(r) for r in again.records]


def test_surrogate_descriptor_carries_engine_id(tmp_path):
    spec = _small_spec(seeds=(0,))
    run_surrogate(spec, tmp_path)
    for cell in spec.cells():
        meta = json.loads(
            (tmp_path / surrogate_hash(cell) / "meta.json").read_text())
        assert meta["engine"] == SURROGATE_ENGINE_ID
        d = surrogate_descriptor(cell)
        d.pop("engine")
        assert d == cell.descriptor()


def test_surrogate_hash_keys_on_accelerator_platform(monkeypatch):
    """A cell integrated on an accelerator hashes apart from the CPU's, so
    neither is ever served the other's result; CPU hashes stay unkeyed."""
    import repro.experiments.surrogate as sur_mod
    cell = next(_small_spec(seeds=(0,)).cells())
    cpu = surrogate_hash(cell)
    assert "platform" not in surrogate_descriptor(cell)
    monkeypatch.setattr(sur_mod, "_device_platform", lambda: "tpu")
    assert surrogate_descriptor(cell)["platform"] == "tpu"
    assert surrogate_hash(cell) != cpu


def test_compile_cache_dir_is_fixed_unless_env_names_one(monkeypatch):
    """Unset, the persistent compile cache sits at <checkout>/.jax_cache;
    with JAX_COMPILATION_CACHE_DIR set, the helper sets no directory."""
    import jax
    import repro.simcluster.surrogate as sg
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        checkout = Path(__file__).resolve().parents[1]
        assert sg.use_compile_cache() == str(checkout / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", prev)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/env")
        assert sg.use_compile_cache() == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_unsupported_grid_rejected_before_any_work(tmp_path):
    spec = _small_spec(schedulers=("proposed", "adaptive"))
    with pytest.raises(SurrogateUnsupported):
        run_surrogate(spec, tmp_path)
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------------
# property pins (fuzz tier)
# ---------------------------------------------------------------------------

_LOWERABLE = ["proposed", "fair", "fifo", "delay", "edf_nopark"]


@pytest.mark.fuzz
@pytest.mark.parametrize("policy", _LOWERABLE)
def test_batch_of_one_matches_its_lane_in_a_mixed_batch(policy):
    """A cell alone answers exactly as it does in one vmapped batch with
    cells of every other lowerable policy."""
    cells = [_cell(policy=p) for p in _LOWERABLE]
    assert len({(c.padded_jobs(), c.n_steps()) for c in cells}) == 1
    lane = _LOWERABLE.index(policy)
    assert _fingerprint(run_batch([cells[lane]])[0]) == \
        _fingerprint(run_batch(cells)[lane])


@pytest.mark.fuzz
def test_batch_order_and_size_invariance():
    """Results depend only on each cell's own inputs — never on batch
    composition.  Mixed presets force mixed padding buckets."""
    cells = [_cell(policy=p, seed=s, preset=pr)
             for p, s, pr in [("proposed", 0, "mix_small"),
                              ("fair", 1, "mix_small"),
                              ("delay", 2, "heavy_tail"),
                              ("fifo", 0, "heavy_tail"),
                              ("edf_nopark", 3, "mix_small"),
                              ("proposed", 1, "heavy_tail")]]
    base = [_fingerprint(r) for r in run_batch(cells)]
    flipped = [_fingerprint(r) for r in run_batch(cells[::-1])][::-1]
    assert base == flipped
    chunked = [_fingerprint(r) for chunk in (cells[:2], cells[2:5], cells[5:])
               for r in run_batch(chunk)]
    assert base == chunked


@pytest.mark.fuzz
def test_max_batch_override_is_result_invariant(monkeypatch):
    """The sub-batch cap only decides how the vmap is split: a smaller
    cap resplits it without moving a single byte of output."""
    import repro.simcluster.surrogate as sg
    assert sg._MAX_BATCH == 64                       # pinned default
    cells = [_cell(policy=p, seed=s)
             for p, s in [("proposed", 0), ("fair", 1), ("fifo", 2),
                          ("delay", 0), ("proposed", 3)]]
    base = [_fingerprint(r) for r in run_batch(cells)]
    for cap in (1, 2, 3):
        monkeypatch.setattr(sg, "_MAX_BATCH", cap)
        assert base == [_fingerprint(r) for r in run_batch(cells)], cap


@pytest.mark.fuzz
@pytest.mark.parametrize("seed", [0, 7])
def test_byte_determinism_per_config_seed(seed):
    """Two fresh integrations of the same (config, seed) — including a
    fresh XLA trace — agree byte-for-byte on cpu."""
    import repro.simcluster.surrogate as sg
    a = _fingerprint(run_batch([_cell(seed=seed)])[0])
    sg._KERNEL_CACHE.clear()
    b = _fingerprint(run_batch([_cell(seed=seed)])[0])
    assert a == b


@pytest.mark.fuzz
def test_seed_and_policy_actually_move_the_result():
    def answer(**kw):
        return _fingerprint(run_batch([_cell(**kw)])[0])

    base = answer(policy="proposed", seed=0)
    assert answer(policy="proposed", seed=1) != base
    assert answer(policy="fifo", seed=0) != base


@pytest.mark.fuzz
def test_every_unsupported_registry_policy_raises():
    """The registry partitions cleanly: the adaptive pressure EWMAs (and
    the harvest preset built on them) are the only oracle-only
    components, and each rejection is typed + attributed rather than a
    silent approximation."""
    supported, rejected = partition_policies(surrogate_supported)
    assert supported == ["proposed", "fair", "fifo", "delay", "edf_nopark"]
    assert rejected == ["adaptive", "adaptive_ra", "harvest"]
    for name in rejected:
        with pytest.raises(SurrogateUnsupported) as exc:
            lower_policy(PolicySpec.parse(name))
        assert exc.value.axis in ("park", "overload")
        assert exc.value.label == name
    for name in supported:
        lower_policy(name)


# ---------------------------------------------------------------------------
# cell inputs built without placing blocks
# ---------------------------------------------------------------------------

def _placed_build_cell(trace, cluster, policy, seed):
    """``build_cell`` as it was while it placed every block: job specs
    with their HDFS placements, ``c_repl`` each job's mean count of
    distinct holders a block."""
    jobs = trace.job_specs(cluster)
    n = len(jobs)
    rng = np.random.default_rng(
        _stable_seed("surrogate-jitter", trace.name, trace.seed, seed))
    submit = np.array([j.submit_time for j in jobs], np.float32)
    dl_rel = np.array([j.deadline for j in jobs], np.float32)
    u_m = np.array([j.u_m for j in jobs], np.float32)
    v_r = np.array([j.v_r for j in jobs], np.float32)
    map_t = np.empty(n, np.float32)
    red_t = np.empty(n, np.float32)
    c_repl = np.empty(n, np.float32)
    for i, j in enumerate(jobs):
        prof = j.profile
        cv = getattr(prof, "time_cv", 0.08)
        z_m, z_r = rng.standard_normal(2)
        jitter_m = math.exp(cv * z_m / math.sqrt(max(j.u_m, 1)))
        jitter_r = math.exp(cv * z_r / math.sqrt(max(j.v_r, 1)))
        map_t[i] = prof.map_time * TAIL_INFLATION * jitter_m
        red_t[i] = ((prof.reduce_time + j.u_m * prof.shuffle_time_per_pair)
                    * TAIL_INFLATION * jitter_r)
        c_repl[i] = float(np.mean(
            [len(set(p)) for p in j.block_placement[:j.u_m]]))
    remote_mult = 1.0 + (jobs[0].profile.remote_penalty
                         * cluster.remote_penalty_scale)
    map_slots = float(cluster.num_nodes * cluster.base_map_slots)
    red_slots = float(cluster.num_nodes * cluster.base_reduce_slots)
    total_work = (float(np.sum(u_m * map_t)) * remote_mult / map_slots
                  + float(np.sum(v_r * red_t)) / red_slots)
    adaptive = cluster.adaptive if isinstance(cluster.adaptive,
                                              AdaptiveConfig) else AdaptiveConfig()
    return SurrogateCellInputs(
        submit=submit, dl_abs=submit + dl_rel, u_m=u_m, v_r=v_r,
        map_t=map_t, red_t=red_t, c_repl=c_repl,
        n_nodes=cluster.num_nodes, n_machines=cluster.num_machines,
        map_slots=map_slots, red_slots=red_slots, remote_mult=remote_mult,
        policy=lower_policy(policy),
        overload_pending_factor=adaptive.overload_pending_factor,
        overload_active_factor=adaptive.overload_active_factor,
        horizon=float(np.max(submit)) + 3.0 * total_work + 900.0,
        job_ids=[j.job_id for j in jobs],
        workloads=[j.profile.name for j in jobs],
        input_gb=[j.input_size_gb for j in jobs],
        deadlines_rel=dl_rel)


def _job_type_trace():
    """A job-type stream shaped like FB-2009's: mostly one-block sorts,
    a few large greps and a transform, skewed placement (the default)."""
    types = [("sort", 2.1e-05)] * 30 + [("sort", 0.000381)] * 4 + [
        ("grep", 23.0), ("wordcount", 25.5), ("inverted_index", 4.18)]
    rows = [(w, gb, default_deadline(w, gb), 55.0 * i)
            for i, (w, gb) in enumerate(types)]
    return trace_from_rows("fb2009-like", rows, seed=11)


_PLACEMENT_CASES = {
    # the paper's cell: skewed placement, one replica a block
    "heavy_tail_20x2_r1": (
        lambda: generate_trace(PRESETS["heavy_tail"], seed=3),
        ClusterSpec(num_machines=20, vms_per_machine=2, replication=1)),
    # a fleet-scale job-type stream, triple replication
    "job_types_600x2_r3": (
        _job_type_trace,
        ClusterSpec(num_machines=600, vms_per_machine=2, replication=3)),
    # uniform placement
    "uniform_skew0_6x2_r3": (
        lambda: generate_trace(
            dataclasses.replace(PRESETS["mix_small"], skew=0.0), seed=1),
        ClusterSpec(num_machines=6, vms_per_machine=2, replication=3)),
    # more replicas than VMs: each block on every VM
    "replication_above_nodes": (
        lambda: generate_trace(PRESETS["mix_small"], seed=2),
        ClusterSpec(num_machines=1, vms_per_machine=2, replication=3)),
}


@pytest.mark.parametrize("case", sorted(_PLACEMENT_CASES))
def test_build_cell_is_byte_identical_to_the_placed_build(case):
    """Building rows from the trace, with ``c_repl`` the cluster's distinct
    holders, gives every input the placed build gave: each array byte for
    byte, each list and scalar by value."""
    make_trace, cluster = _PLACEMENT_CASES[case]
    trace = make_trace()
    for policy, seed in (("proposed", 0), ("edf_nopark", 5)):
        got = build_cell(trace, cluster, policy, seed)
        want = _placed_build_cell(trace, cluster, policy, seed)
        for f in dataclasses.fields(SurrogateCellInputs):
            a, b = getattr(got, f.name), getattr(want, f.name)
            if isinstance(b, np.ndarray):
                assert (a.dtype, a.shape) == (b.dtype, b.shape), f.name
                assert a.tobytes() == b.tobytes(), f.name
            else:
                assert type(a) is type(b) and a == b, f.name


def test_build_cell_places_no_block(monkeypatch):
    """The surrogate path never calls ``place_blocks``: with it raising,
    ``build_cell`` and ``build_inputs`` still build every cell, while the
    event engine's ``job_specs`` does reach it."""
    from repro.experiments.surrogate import build_inputs
    from repro.simcluster import traces

    def place_blocks(*args, **kw):
        raise AssertionError("a block was placed")

    monkeypatch.setattr(traces, "place_blocks", place_blocks)
    trace = generate_trace(PRESETS["mix_small"], seed=0)
    cell = build_cell(trace, _CLUSTER, "proposed", 0)
    assert cell.n_jobs == len(trace.jobs)
    assert (cell.c_repl == 1.0).all()
    cells = list(_small_spec().cells())
    _, inputs = build_inputs(cells)
    assert len(inputs) == len(cells) == 4
    with pytest.raises(AssertionError, match="a block was placed"):
        trace.job_specs(_CLUSTER)


# ---------------------------------------------------------------------------
# jobs packed in priority order
# ---------------------------------------------------------------------------

def _gather_alloc(demand, capacity, key):
    """Strict-priority allocation by permutation, as the kernel made it
    before jobs came packed in priority order: gather the demand into key
    order, fill, scatter the allocation back."""
    import jax.numpy as jnp
    order = np.argsort(key, kind="stable")
    d = demand[order]
    filled = np.asarray(jnp.clip(capacity - (jnp.cumsum(d) - d), 0.0, d))
    alloc = np.empty_like(filled)
    alloc[order] = filled
    return alloc


@pytest.mark.parametrize("case", ["random", "ties", "zero_demand",
                                  "capacity_above_total", "whole_numbers"])
def test_priority_alloc_on_sorted_rows_equals_the_gather_form(case):
    import jax.numpy as jnp
    import repro.simcluster.surrogate as sg
    rng = np.random.default_rng(14)
    n = 257
    demand = rng.uniform(0.0, 7.0, n).astype(np.float32)
    key = rng.permutation(n).astype(np.float32)
    capacity = np.float32(0.4 * demand.sum())
    if case == "ties":
        key = rng.integers(0, 9, n).astype(np.float32)
    elif case == "zero_demand":
        demand[rng.random(n) < 0.4] = 0.0
    elif case == "capacity_above_total":
        capacity = np.float32(2.0 * demand.sum())
    elif case == "whole_numbers":
        demand = np.floor(demand)
    order = np.argsort(key, kind="stable")
    packed = np.asarray(sg._priority_alloc(jnp, demand[order], capacity))
    alloc = np.empty_like(packed)
    alloc[order] = packed
    expected = _gather_alloc(demand, capacity, key)
    assert alloc.tobytes() == expected.tobytes()
    if case == "whole_numbers":   # every partial sum exact in float32
        d = demand[order]
        plain = np.clip(capacity - (np.cumsum(d) - d), 0.0, d)
        assert packed.tobytes() == plain.astype(np.float32).tobytes()
    if case == "capacity_above_total":
        assert alloc.tobytes() == demand.tobytes()


def _shuffled(cell, perm):
    """The same cell with its jobs listed in ``perm``'s order."""
    import dataclasses
    arrays = ("submit", "dl_abs", "u_m", "v_r", "map_t", "red_t", "c_repl",
              "deadlines_rel")
    lists = ("job_ids", "workloads", "input_gb")
    return dataclasses.replace(
        cell, **{k: getattr(cell, k)[perm] for k in arrays},
        **{k: [getattr(cell, k)[i] for i in perm] for k in lists})


@pytest.mark.parametrize("policy", ["edf_nopark", "fifo", "proposed",
                                    "fair"])
def test_shuffled_cell_packs_and_answers_the_same(policy):
    """Packing follows the priority key, not the jobs' listed order: a
    cell whose jobs come shuffled packs to the same arrays and answers
    the same, job by job, with its jobs in its own order."""
    import repro.simcluster.surrogate as sg
    cell = _cell(policy=policy, preset="heavy_tail")
    key = cell.submit if policy == "fifo" else cell.dl_abs
    assert len(set(key.tolist())) == cell.n_jobs     # distinct keys
    perm = np.random.default_rng(3).permutation(cell.n_jobs)
    shuffled = _shuffled(cell, perm)
    a, b = sg.pack_cell(cell), sg.pack_cell(shuffled)
    assert a.keys() == b.keys()
    for k in a:
        assert np.asarray(a[k]).tobytes() == np.asarray(b[k]).tobytes(), k
    base, moved = run_batch([cell])[0], run_batch([shuffled])[0]
    assert [j.job_id for j in moved.jobs] == shuffled.job_ids
    fp_base, fp_moved = _fingerprint(base), _fingerprint(moved)
    assert fp_moved[:4] == fp_base[:4]
    assert fp_moved[5] == fp_base[5]
    # the locality rate sums the same launches in another order
    assert fp_moved[4] == pytest.approx(fp_base[4], rel=1e-12)
    assert fp_moved[6] == tuple(fp_base[6][i] for i in perm)


def test_priority_order_keeps_ties_in_index_order_and_padding_last():
    import dataclasses
    import jax.numpy as jnp
    import repro.simcluster.surrogate as sg
    cell = _cell(policy="edf_nopark", preset="heavy_tail")
    # deadlines in a few tied groups, out of index order
    dl_abs = (1000.0 * (np.arange(cell.n_jobs) % 5)[::-1]).astype(np.float32)
    cell = dataclasses.replace(cell, dl_abs=dl_abs)
    order = sg.priority_order(cell)
    for i, j in zip(order[:-1], order[1:]):
        assert dl_abs[i] < dl_abs[j] or (dl_abs[i] == dl_abs[j] and i < j)
    packed = sg.pack_cell(cell)
    n, jp = cell.n_jobs, cell.padded_jobs()
    assert jp > n
    assert packed["pad_mask"].tolist() == [1.0] * n + [0.0] * (jp - n)
    assert (packed["dl_abs"][n:] == sg._INF).all()
    assert packed["dl_abs"][:n].tobytes() == dl_abs[order].tobytes()
    # the order a stable argsort of the padded key gives: padding last
    padded_key = np.full(jp, sg._INF, np.float32)
    padded_key[:n] = dl_abs
    assert np.asarray(jnp.argsort(padded_key)).tolist() == \
        order.tolist() + list(range(n, jp))


# ---------------------------------------------------------------------------
# the loop counter and the stage map
# ---------------------------------------------------------------------------

def _stacked(cells):
    import repro.simcluster.surrogate as sg
    packed = [sg.pack_cell(c) for c in cells]
    return {k: np.stack([q[k] for q in packed]) for k in packed[0]}


def test_chunks_count_each_lanes_own_loop():
    """In a batch of cells that finish in different chunks, each lane
    reports the chunks its own early exit ran: through the chunk that
    holds its last finish, or every chunk for a cell that never finishes."""
    import dataclasses
    import repro.simcluster.surrogate as sg
    base = _cell(policy="fair")
    horizon = 8 * sg.CHUNK * sg.DT
    cells = [dataclasses.replace(base, submit=base.submit + shift,
                                 dl_abs=base.dl_abs + shift, horizon=horizon)
             for shift in (0.0, 3000.0, 7000.0, 2 * horizon)]
    jp, ts = cells[0].padded_jobs(), cells[0].n_steps()
    assert {(c.padded_jobs(), c.n_steps()) for c in cells} == {(jp, ts)}
    out = sg._compiled(jp, ts)(_stacked(cells))
    chunks = np.asarray(out["chunks"])
    assert chunks.dtype == np.int32
    for lane, cell in enumerate(cells):
        finish = np.asarray(out["finish"][lane][:cell.n_jobs])
        if (finish < float(sg._INF)).all():
            last_step = int(round(float(finish.max()) / sg.DT)) - 1
            expected = last_step // sg.CHUNK + 1
        else:
            expected = ts // sg.CHUNK
        assert chunks[lane] == expected, lane
    assert chunks.tolist() == [1, 3, 5, 8]


def test_results_ignore_the_chunk_counter():
    """``_unpack_result`` reads the same result with or without the new
    output, so records stay what they were."""
    import repro.simcluster.surrogate as sg
    cell = _cell(policy="proposed")
    out = sg._compiled(cell.padded_jobs(), cell.n_steps())(_stacked([cell]))
    out = {k: np.asarray(v)[0] for k, v in out.items()}
    without = {k: v for k, v in out.items() if k != "chunks"}
    assert _fingerprint(sg._unpack_result(cell, out)) == \
        _fingerprint(sg._unpack_result(cell, without))


def test_kernel_stages_name_the_ring_ops():
    import repro.simcluster.surrogate as sg
    stages = sg.kernel_stages(8, sg.CHUNK, 2)
    found = set(stages.values())
    assert {"ring_drain", "ring_scatter"} <= found
    assert found <= set(sg.KERNEL_STAGES) | {sg.UNSCOPED}
    assert all(not name.startswith("%") for name in stages)


_HLO = """\
HloModule m

%fused_computation.1 (p0: f32[4,64]) -> (f32[], f32[4]) {
  %p0 = f32[4,64]{1,0} parameter(0)
  %c = f32[] constant(0)
  %total = f32[] reduce(%p0, %c), dimensions={0,1}, to_apply=%add_r, metadata={op_name="jit(k)/vmap()/while/body/map_alloc/reduce_sum"}
  %rows = f32[4]{0} reduce(%p0, %c), dimensions={1}, to_apply=%add_r, metadata={op_name="jit(k)/vmap()/while/body/ring_drain/reduce_sum"}
  ROOT %t = (f32[], f32[4]{0}) tuple(%total, %rows)
}

%add_r (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b), metadata={op_name="ring_scatter/add"}
}

%fused_computation.2 (q0: f32[256], q1: s32[4], q2: f32[4]) -> f32[256] {
  %q0 = f32[256]{0} parameter(0)
  %q1 = s32[4]{0} parameter(1)
  %q2 = f32[4]{0} parameter(2)
  ROOT %scatter.9 = f32[256]{0} scatter(%q0, %q1, %q2), to_apply=%add_r
}

ENTRY %main (x: f32[4,64], i: s32[4], u: f32[4]) -> f32[256] {
  %x = f32[4,64]{1,0} parameter(0)
  %i = s32[4]{0} parameter(1)
  %u = f32[4]{0} parameter(2)
  %sort.0 = f32[4,64]{1,0} sort(%x), dimensions={1}, metadata={op_name="jit(k)/vmap(setup)/jit(argsort)/sort"}
  %fusion.1 = (f32[], f32[4]{0}) fusion(%sort.0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(k)/vmap()/while/body/map_alloc/reduce_sum"}
  %copy.3 = f32[4,64]{0,1} copy(%sort.0)
  %bitcast.4 = f32[256]{0} bitcast(%copy.3)
  %fusion.2 = f32[256]{0} fusion(%bitcast.4, %i, %u), kind=kCustom, calls=%fused_computation.2
  %while.5 = f32[256]{0} while(%fusion.2), condition=%add_r, body=%add_r, metadata={op_name="jit(k)/vmap()/while"}
  ROOT %copy.6 = f32[256]{0} copy(%while.5)
}
"""


def test_hlo_stages_attribution_rules():
    """A scope shows under ``vmap(...)``; a multi-output fusion takes its
    largest output's stage; a rewritten scatter without metadata takes its
    combiner's; a layout copy its operand's; control flow stays unscoped."""
    import repro.simcluster.surrogate as sg
    stages = sg.hlo_stages(_HLO)
    assert stages["sort.0"] == "setup"
    assert stages["fusion.1"] == "ring_drain"
    assert stages["scatter.9"] == "ring_scatter"
    assert stages["fusion.2"] == "ring_scatter"
    assert stages["copy.3"] == "setup"
    assert stages["while.5"] == sg.UNSCOPED
    assert stages["copy.6"] == sg.UNSCOPED
    assert stages["x"] == sg.UNSCOPED
