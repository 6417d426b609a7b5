"""The trace reduction on a small synthetic trace, and the peaks table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"
# a kernel's op as the trace names it: its HLO text
KV = "%posit_kv_attention_batched.6 = f32[16,8,4,128] custom-call(...)"


def _planes(marker_ns=1_000_000.0):
    """A host plane with the window marker, and one device whose ops sit
    at known offsets from it (ns on the trace's own clock)."""
    m = marker_ns
    return [
        ("/host:CPU", {"python": [(tr.MARKER, m, 10.0),
                                  ("other", m + 5e6, 1e6)]}),
        (DEV, {tr.OPS_LINE: [
            ("fusion.1", m + 0.0, 2e6),                 # [0, 2) ms
            (KV, m + 1e6, 2e6),                         # [1, 3) overlaps
            (KV, m + 5e6, 1e6),                         # [5, 6) ms
            ("late", m + 20e6, 1e6),                    # after the window
        ], "XLA Modules": [("jit_step", m, 6e6)]}),
        ("/device:TPU:0 SparseCore", {}),
    ]


def test_ops_land_on_the_host_clock_and_busy_is_a_union():
    host_marker = 100.0          # perf_counter seconds at the marker
    t = tr.reduce_planes(_planes(), host_marker, host_marker,
                         host_marker + 0.010)
    assert set(t.ops) == {DEV}
    name, a, b = t.ops[DEV][0]
    assert name == "fusion.1" and a == pytest.approx(100.0)
    # [0, 3) and [5, 6) ms: 4 ms busy of a 10 ms window
    assert t.busy_s() == pytest.approx(0.004)
    assert t.idle_share() == pytest.approx(0.6)
    assert t.window_s == pytest.approx(0.010)


def test_kernel_time_sums_its_calls_inside_the_window():
    t = tr.reduce_planes(_planes(), 0.0, 0.0, 0.010)
    secs, calls = t.kernel_time("posit_kv_attention")
    assert calls == 2 and secs == pytest.approx(0.003)
    # a window that cuts a call keeps the part inside
    t2 = tr.reduce_planes(_planes(), 0.0, 0.0, 0.0055)
    assert t2.kernel_time("posit_kv_attention")[0] == pytest.approx(0.0025)
    assert t2.kernel_time("nothing") == (0.0, 0)


def test_an_op_that_reads_a_kernels_output_is_not_kernel_time():
    # the trace names an op by its HLO text, operands included: a fusion
    # that consumes the kernel's result names the kernel too
    consumer = ("%fusion.9 = f32[16,4096]{1,0} fusion(f32[16,8,4,128] "
                "%posit_kv_attention_batched.6), kind=kLoop")
    planes = _planes()
    planes[1][1][tr.OPS_LINE].append((consumer, 1_000_000.0 + 7e6, 1e6))
    t = tr.reduce_planes(planes, 0.0, 0.0, 0.010)
    secs, calls = t.kernel_time("posit_kv_attention")
    assert calls == 2 and secs == pytest.approx(0.003)
    assert tr.instruction(KV) == "posit_kv_attention_batched.6"
    assert not tr.is_kernel(consumer, "posit_kv_attention")


def test_breakdown_names_the_longest_ops_and_gaps():
    t = tr.reduce_planes(_planes(), 0.0, 0.0, 0.010)
    bd = t.breakdown([("serve/decode", 0.0031, 0.0049),
                      ("frame/decode", 0.006, 0.0061)], n=10)
    ops = dict(bd["device_ops"])
    assert ops[KV] == pytest.approx(0.003)
    assert "late" not in ops
    # gaps: [3, 5) ms covered by serve/decode, [6, 10) ms mostly by nothing
    assert bd["idle_gaps"][0][1] == pytest.approx(0.004)
    assert bd["idle_gaps"][1] == ["serve/decode", pytest.approx(0.002)]


def test_a_trace_without_the_marker_is_refused():
    planes = [(DEV, {tr.OPS_LINE: [("x", 0.0, 1.0)]})]
    with pytest.raises(ValueError):
        tr.reduce_planes(planes, 0.0, 0.0, 1.0)


def test_union_length_merges_overlaps():
    assert tr.union_length([]) == 0.0
    assert tr.union_length([(0, 1), (0.5, 2), (3, 4), (3.5, 3.6)]) == 3.0


def test_roofline_share_names_its_bound():
    peaks = tr.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    # 819 MB in 2 ms: the memory bound is 1 ms, so 50 %
    share, bound = tr.roofline_share(1e9, 819e6, 0.002, peaks)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = tr.roofline_share(197e12, 0.0, 2.0, peaks)
    assert bound == "compute" and share == pytest.approx(50.0)


def test_unknown_device_is_an_error():
    with pytest.raises(tr.UnknownDevice):
        tr.peaks_for("cpu")
    assert "Google Cloud" in tr.load_peaks()["source"]


def test_describe_counts_event_names():
    d = tr.describe(_planes())
    assert d[DEV][tr.OPS_LINE]["events"] == 4
    assert d[DEV][tr.OPS_LINE]["names"][0] == (KV, 2)
