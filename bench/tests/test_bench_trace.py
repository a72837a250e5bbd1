"""The trace reduction (``lib/trace.py``): busy time as a union, idle gaps
named by the host span open in them, device time by program and kernel,
and collective time that no compute hides."""
import os

import pytest

from lib import common, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000                  # nanoseconds


def small():
    """Two devices; times in ms.  Device 0 runs a fusion 0-4, a Pallas
    kernel 3-6 (overlapping, another program), and after a gap (6-10, host
    in "step") a loop 10-14 around an all-gather 10-14 of which 12-13 is
    hidden by a fusion.  Device 1 is busy 0-2."""
    d0 = [["fusion.1", 0, 4 * MS, "jit_step", "op"],
          ["attention.3", 3 * MS, 3 * MS, "jit_prefill_step", "kernel"],
          ["while.2", 10 * MS, 4 * MS, "jit_step", "op"],
          ["all-gather-start.1", 10 * MS, 4 * MS, "jit_step", "collective"],
          ["fusion.2", 12 * MS, 1 * MS, "jit_step", "op"]]
    d1 = [["fusion.1", 0, 2 * MS, "jit_step", "op"]]
    host = [["step", 0, 20 * MS], ["decode", 6 * MS, 1 * MS]]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "host": host}


def test_union_subtract_gaps():
    assert trace.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert trace.length([[0, 3], [5, 8]]) == 6
    assert trace.subtract([[0, 10]], [[2, 3], [5, 7]]) == [
        [0, 2], [3, 5], [7, 10]]
    assert trace.gaps([[1, 2], [4, 5]], 0, 6) == [[0, 1], [2, 4], [5, 6]]


def test_small_trace_summary():
    s = trace.summarize(small(), window_s=0.020)
    # device 0 busy 0-6 and 10-14 = 10 ms, device 1 2 ms: mean 6 ms
    assert s["busy_s"] == pytest.approx(0.006)
    assert s["programs_s"]["jit_prefill_step"] == pytest.approx(0.0015)
    assert s["kernels_s"] == {"jit_prefill_step": pytest.approx(0.0015)}
    assert trace.seconds_in(s, "ops_s", r"^jit_step/all-gather") == \
        pytest.approx(0.002)
    # the loop contains the all-gather: counted in busy time only
    assert not any("while" in k for k in s["ops_s"])
    # all-gather 10-14 ms, 12-13 hidden by fusion.2: 3 ms exposed on
    # device 0, none on device 1, averaged over the two
    assert s["collective_s"] == pytest.approx(0.002)
    assert s["exposed_collective_s"] == pytest.approx(0.0015)
    # the one gap of device 0 (6-10 ms) is named by the span open at its
    # midpoint, 8 ms: "step" ("decode" ended at 7)
    assert s["breakdown"]["idle_gaps"] == [["step", pytest.approx(0.004)]]
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["jit_step/fusion.1"] == pytest.approx(0.003)   # (4 + 2) / 2


def test_recorded_chip_trace():
    """An excerpt of a trace taken on a v5e: a 4096^2 bf16 matmul three
    times inside the host span "mm", then the flash-attention forward kernel
    three times inside "fa"."""
    rec = common.load_json(DATA, "trace_v5e.json")
    s = trace.summarize(rec["trace"], rec["window_s"])
    want = rec["expected"]
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    assert sum(s["kernels_s"].values()) == pytest.approx(
        want["kernel_s"], rel=1e-9)
    assert [g[0] for g in s["breakdown"]["idle_gaps"]][:1] == \
        want["longest_gap_span"]
