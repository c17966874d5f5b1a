"""The trace reduction: interval arithmetic, and a trace recorded on one
TPU v5e chip (a traced window of two queries of ``query.paper_20x2``)."""
from pathlib import Path

import pytest

from harness import program, xtrace

FIXTURE = Path(__file__).parent / "fixtures" / "query_v5e.xplane.pb.gz"


def test_merge_and_covered():
    merged = xtrace.merge([(5, 6), (0, 1), (0.5, 2), (2, 3)])
    assert merged == [(0, 3), (5, 6)]
    assert xtrace.covered(merged, 1, 5.5) == pytest.approx(2.5)
    assert xtrace.covered(merged, 3, 5) == 0.0
    assert xtrace.covered([], 0, 1) == 0.0


def test_idle_gaps_named_by_host_span():
    r = xtrace.Reduced(busy={"/device:TPU:0": [(1.0, 2.0), (4.0, 4.5)]},
                       spans={"bench.build": [(2.0, 3.9)],
                              "bench.request": [(0.0, 5.0)]})
    gaps = r.idle_gaps((0.0, 5.0))
    assert gaps[0] == ["bench.build", pytest.approx(2.0)]
    assert sorted(g[1] for g in gaps) == pytest.approx([0.5, 1.0, 2.0])
    assert r.busy_s(0.0, 5.0) == pytest.approx(1.5)
    assert r.busy_in([(0.0, 1.5), (1.2, 4.2)]) == pytest.approx(1.2)


@pytest.fixture(scope="module")
def recorded():
    return xtrace.reduce_file(str(FIXTURE))


def test_recorded_trace_spans_and_modules(recorded):
    """Three queries: each a request span holding one build span and one
    ``run_batch`` span, and one run of the surrogate executable."""
    for name in (program.SPAN_REQUEST, program.SPAN_BUILD,
                 program.SPAN_ENGINE):
        assert len(recorded.spans[name]) == 3
    assert [m[0].split("(")[0] for m in recorded.modules] == ["jit_kernel"] * 3
    assert list(recorded.busy) == ["/device:TPU:0"]


def test_recorded_trace_reduction(recorded):
    window = recorded.window(program.SPAN_WINDOW)
    assert window[1] - window[0] == pytest.approx(0.270438864, abs=1e-9)
    busy = recorded.busy_s(*window)
    assert busy == pytest.approx(0.126037619, abs=1e-9)
    # the device works only inside run_batch, and only in the executable
    assert recorded.busy_in(recorded.spans[program.SPAN_ENGINE]) \
        == pytest.approx(busy, abs=1e-9)
    assert recorded.module_seconds("jit_kernel", window) \
        == pytest.approx(0.126038675, abs=1e-9)
    # exclusive op times add up to the busy time, not more
    assert sum(recorded.op_seconds.values()) == pytest.approx(busy, rel=0.02)
    assert recorded.top_ops(1)[0][0] == "%fusion.209"
    # the longest gaps are the host build; seams under a microsecond
    # between back-to-back ops are not gaps
    gaps = recorded.idle_gaps(window)
    assert gaps[0][0] == program.SPAN_BUILD
    assert sum(g for _, g in gaps) == pytest.approx(
        window[1] - window[0] - busy, abs=1e-5)
