"""trace_reduce.py on a small trace recorded on the chip (one TPU v5
lite, PR 24): three calls of a jitted scan of four matmuls followed by a
Pallas kernel named ``tiny_add_kernel``, 2 ms pauses between the calls,
under the benchmark's spans.  Read by hand first (``--describe``): one
device plane with "XLA Modules" / "XLA Ops", the spans on the host's
"python" line.  The device's clock runs about 1.5 ms ahead of the
host's there, so the first call's program lies before ``bench/window``
opens: two of the three runs are inside the window."""
import os

import pytest

from benchmark import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_tpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(TRACE, kernels={"tiny_add": "tiny_add_kernel"})


def test_busy_union_and_idle_share(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.010082446, rel=1e-6)
    # two program runs inside the window, about 9.3 us each
    assert reduced["modules"]["jit_step"]["count"] == 2
    assert reduced["modules"]["jit_step"]["total_s"] == \
        pytest.approx(18.536e-6, rel=1e-3)
    # the ops' union is the programs' time: nested ops are not counted twice
    assert reduced["busy_s"] == pytest.approx(18.503e-6, rel=1e-3)
    assert reduced["busy_s"] <= reduced["modules"]["jit_step"]["total_s"]
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.99816, abs=1e-4)


def test_busy_agrees_with_a_brute_force_union(reduced):
    """The same number from the raw events on a 1 ns grid."""
    pd = tr.load(TRACE)
    lo = hi = None
    ops = []
    for plane in pd.planes:
        for line in plane.lines:
            for a, b, name in tr._events(line):
                if name == tr.WINDOW_SPAN:
                    lo, hi = a, b
                elif plane.name == "/device:TPU:0" and \
                        line.name == tr.OPS_LINE:
                    ops.append((a, b))
    covered = set()
    for a, b in ops:
        a, b = max(a, lo), min(b, hi)
        covered.update(range(round(a * 1e9), round(b * 1e9)))
    assert reduced["busy_s"] == pytest.approx(len(covered) * 1e-9, rel=2e-3)


def test_per_op_self_time_sums_to_busy(reduced):
    ops = reduced["ops"]
    whiles = [k for k in ops if k.startswith("%while")]
    assert len(whiles) == 1
    w = ops[whiles[0]]
    # the scan's `while` holds the four matmul fusions: its own part is
    # a sliver of its duration
    assert w["count"] == 2 and w["self_s"] < 0.05 * w["total_s"]
    assert sum(o["self_s"] for o in ops.values()) == \
        pytest.approx(reduced["busy_s"], rel=1e-6)
    groups = dict(reduced["device_ops"])
    assert max(groups, key=groups.get) == "fusion:kOutput -> bf16[512,512]"


def test_a_named_kernel(reduced):
    # two runs of the Pallas kernel inside the window, 1.2 us each
    assert reduced["kernel_s"]["tiny_add"] == pytest.approx(2.357e-6,
                                                            rel=1e-3)
    assert "custom-call:tpu_custom_call -> bf16[512,512]" in \
        dict(reduced["device_ops"])


def test_idle_gaps_are_named_by_the_benchmarks_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) == {"bench/pause", "bench/call"}
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-3)


def test_union_clip_and_self_times_on_made_up_intervals():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == \
        [(0, 3), (5, 7)]
    assert tr.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.total([(2, 3), (5, 6)]) == 2
    st = tr.self_times([(0, 10, "outer"), (1, 4, "a"), (2, 3, "b"),
                        (5, 9, "a")])
    assert st["outer"] == [3, 10, 1]
    assert st["a"] == [6, 7, 2] and st["b"] == [1, 1, 1]


def test_op_group_drops_numbers_operands_and_layouts():
    name = ("%fusion.4548 = (bf16[256,128,3072]{2,1,0:T(8,128)(2,1)}, "
            "bf16[256,128,3072]{2,1,0:T(8,128)(2,1)}) fusion(bf16[3072]"
            "{0:T(1024)(128)(2,1)S(1)} %copy-done.995), kind=kOutput, "
            "calls=%fused_computation.476.clone.clone")
    assert tr.op_group(name) == \
        "fusion:kOutput -> (bf16[256,128,3072], bf16[256,128,3072])"
    assert tr.op_group("not an instruction") == "not an instruction"
