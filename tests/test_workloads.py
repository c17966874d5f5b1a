"""Unit coverage for the simcluster workload helpers."""
import math
import random

import pytest

from repro.core.types import ClusterSpec
from repro.simcluster.workloads import (PAPER_TABLE2_ROWS, WORKLOADS,
                                        default_deadline, distinct_holders,
                                        make_job, n_map_tasks,
                                        n_reduce_tasks, paper_cluster,
                                        paper_job_mix, paper_table2_jobs,
                                        place_blocks, task_counts)


def test_n_map_tasks_block_math():
    assert n_map_tasks(1.0) == 8          # 128 MB blocks: 8 per GB
    assert n_map_tasks(10.0) == 80
    assert n_map_tasks(1.01) == 9         # partial block => extra map task
    assert n_map_tasks(0.05) == 1         # tiny inputs still get one task
    assert n_map_tasks(0.0) == 1


def test_task_counts_pair_the_map_and_reduce_counts():
    for w in WORKLOADS:
        for gb in (0.0, 2.1e-05, 1.01, 230.0):
            assert task_counts(w, gb) == (n_map_tasks(gb),
                                          n_reduce_tasks(w, gb))


def test_n_reduce_tasks_ratio_and_floor():
    for w in WORKLOADS:
        assert n_reduce_tasks(w, 0.05) >= 1
    # sort: v_r = 0.5 * u_m
    assert n_reduce_tasks("sort", 10.0) == 40
    # permutation is reduce-heavy relative to grep at equal size
    assert n_reduce_tasks("permutation", 4.0) > n_reduce_tasks("grep", 4.0)


def test_default_deadline_monotone_in_size_and_slack():
    for w in WORKLOADS:
        d_small = default_deadline(w, 2.0)
        d_big = default_deadline(w, 10.0)
        assert 0 < d_small < d_big
        assert default_deadline(w, 2.0, slack=4.0) > d_small


def test_make_job_fields_and_placement():
    spec = paper_cluster()
    rng = random.Random(0)
    job = make_job("j0", "wordcount", 5.0, 520.0, spec, rng,
                   submit_time=30.0, skew=1.0)
    assert job.job_id == "j0"
    assert job.profile is WORKLOADS["wordcount"]
    assert job.u_m == n_map_tasks(5.0)
    assert job.v_r == n_reduce_tasks("wordcount", 5.0)
    assert job.deadline == 520.0 and job.submit_time == 30.0
    assert job.input_size_gb == 5.0
    assert len(job.block_placement) == job.u_m
    for placement in job.block_placement:
        # paper cluster: per-VM virtual disks => replication 1
        assert len(placement) == 1
        assert 0 <= placement[0] < spec.num_nodes


_PLACEMENT_CLUSTERS = {
    "defaults": {},
    "4x2_r1": dict(num_machines=4, vms_per_machine=2, replication=1),
    "4x2_r3": dict(num_machines=4, vms_per_machine=2, replication=3),
    "1x2_r3_capped": dict(num_machines=1, vms_per_machine=2, replication=3),
    "600x2_r3": dict(num_machines=600, vms_per_machine=2, replication=3),
}


@pytest.mark.parametrize("skew", [0.0, 1.0])
@pytest.mark.parametrize("cluster", sorted(_PLACEMENT_CLUSTERS))
def test_place_blocks_replication_and_distinctness(cluster, skew):
    """Every block gets exactly ``distinct_holders`` distinct VMs — the
    replication capped by the cluster's VMs — on the uniform and the
    skewed path, from the paper's 8 VMs to a 1,200-VM fleet."""
    spec = ClusterSpec(**_PLACEMENT_CLUSTERS[cluster])
    assert distinct_holders(spec) == min(spec.replication, spec.num_nodes)
    rng = random.Random(1)
    for replication in (None, 1, 2):
        holders = distinct_holders(spec, replication)
        assert holders == min(replication or spec.replication,
                              spec.num_nodes)
        placements = place_blocks(16, spec, rng, replication=replication,
                                  skew=skew)
        assert len(placements) == 16
        for p in placements:
            assert len(p) == holders == len(set(p))     # distinct replicas
            assert all(0 <= n < spec.num_nodes for n in p)


def test_place_blocks_skew_concentrates_load():
    spec = ClusterSpec(num_machines=20, vms_per_machine=2, replication=1)
    rng = random.Random(7)
    flat = place_blocks(400, spec, rng, skew=0.0)
    hot = place_blocks(400, spec, rng, skew=2.0)

    def top_share(placements):
        counts = {}
        for p in placements:
            counts[p[0]] = counts.get(p[0], 0) + 1
        return max(counts.values()) / len(placements)

    assert top_share(hot) > 2 * top_share(flat)


def test_paper_job_mix_construction():
    spec = paper_cluster()
    jobs = paper_job_mix(spec, seed=0)
    assert len(jobs) == 25                      # 5 sizes x 5 workloads
    assert len({j.job_id for j in jobs}) == 25
    submits = [j.submit_time for j in jobs]
    assert submits == sorted(submits) and submits[0] == 0.0
    assert submits[1] - submits[0] == 15.0      # stagger
    sizes = sorted({j.input_size_gb for j in jobs})
    assert sizes == [2, 4, 6, 8, 10]
    # deterministic per seed
    again = paper_job_mix(spec, seed=0)
    assert [j.block_placement for j in again] == [j.block_placement for j in jobs]


def test_paper_table2_jobs_match_rows():
    spec = paper_cluster()
    jobs = paper_table2_jobs(spec, seed=0)
    assert [(j.profile.name, j.input_size_gb, j.deadline) for j in jobs] \
        == [(w, float(gb), dl) for (w, gb, dl) in PAPER_TABLE2_ROWS]
    assert all(j.submit_time == 0.0 for j in jobs)
