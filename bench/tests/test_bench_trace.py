"""Trace reduction: busy union, idle gaps, kernel time, labels."""
import gzip
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_gaps_and_kernel_time():
    k1 = "%refine_compact.1 = (s32[8,256]{1,0}, s32[8,1]{1,0}) custom-call(%a)"
    k2 = "%refine_compact.7 = (s32[8,512]{1,0}, s32[8,1]{1,0}) custom-call(%a)"
    ev = [("%fusion.1 = f32[8]{0} fusion(%refine_compact.1)", 0.0, 10.0),
          (k1, 5.0, 10.0), ("%fusion.2 = f32[8]{0} fusion(%b)", 30.0, 5.0),
          (k2, 40.0, 20.0)]
    assert trace.merged(ev) == [[0.0, 15.0], [30.0, 35.0], [40.0, 60.0]]
    assert trace.busy_ns(ev) == 40.0
    assert trace.idle_gaps(ev, 0.0, 100.0) == [(15.0, 30.0), (35.0, 40.0),
                                               (60.0, 100.0)]
    assert trace.kernel_ns(ev, "refine_compact") == 30.0
    assert trace.short_name(k1) == (
        "refine_compact.1 custom-call (s32[8,256]{1,0}, s32[8,1]{1,0})")
    spans = [("bench.collect", 10.0, 30.0), ("bench.send", 70.0, 40.0)]
    labels = trace.label_gaps(trace.idle_gaps(ev, 0.0, 100.0), spans)
    assert [lb for lb, _ in labels] == ["bench.send", "bench.collect",
                                         "bench.collect"]
    assert [s for _, s in labels] == pytest.approx([40e-9, 15e-9, 5e-9])
    red = trace.reduce({"devices": {"/device:TPU:0": ev}, "spans": spans},
                       100e-9, kernels=["refine_compact"])
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["kernel_s"]["refine_compact"] == pytest.approx(30e-9)
    assert red["device_ops"][0][0].startswith("refine_compact.7 custom-call")
    assert red["device_ops"][0][1] == pytest.approx(20e-9)
    # an op that ran past the end of the slice counts up to the end only
    cut = trace.reduce({"devices": {"/device:TPU:0": ev}, "spans": []},
                       50e-9, kernels=["refine_compact"])
    assert cut["busy_s"] == pytest.approx(30e-9)


def test_no_device_plane_reads_nothing():
    red = trace.reduce({"devices": {}, "spans": []}, 1.0, ["refine_compact"])
    assert red["busy_s"] == 0.0 and red["device_ops"] == []


def test_recorded_chip_trace(tmp_path):
    """A slice of a ``poi4m-knn10-open`` run recorded on a TPU v5e: the
    reduction agrees with an independent sweep over the same events."""
    blob = open(os.path.join(DATA, "knn_slice.xplane.pb.gz"), "rb").read()
    (tmp_path / "slice.xplane.pb").write_bytes(gzip.decompress(blob))
    rec = trace.load(str(tmp_path))
    ev = rec["devices"]["/device:TPU:0"]
    assert len(ev) > 1000
    start = np.array([s for _, s, _ in ev])
    end = start + np.array([d for _, _, d in ev])
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    # a new busy block starts wherever an op begins after every earlier op
    # ended; busy time is the sum of the blocks' lengths
    head = np.r_[True, s[1:] > e[:-1]]
    block_end = np.r_[e[np.nonzero(head)[0][1:] - 1], e[-1]]
    busy = (block_end - s[head]).sum()
    assert trace.busy_ns(ev) == pytest.approx(busy, rel=1e-12)
    kernel = sum(d for n, _, d in ev if n.startswith("%refine_compact."))
    assert kernel > 0
    assert trace.kernel_ns(ev, "refine_compact") == pytest.approx(kernel)
    window_s = end.max() * 1e-9
    red = trace.reduce(rec, window_s, ["refine_compact"])
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < red["kernel_s"]["refine_compact"] <= red["busy_s"] <= window_s
    gaps = trace.idle_gaps(ev, 0.0, window_s * 1e9)
    assert sum(b - a for a, b in gaps) == pytest.approx(
        window_s * 1e9 - busy, rel=1e-9)
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) == 10
    assert any(name.startswith("bench.") for name, _, _ in rec["spans"])
