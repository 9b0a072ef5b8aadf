"""The reduction from a trace to the per-layer numbers: busy union, idle
share, exposed collective time and the kernel <-> HLO-shape join, by hand
on small cases and on records taken from the chip (``testdata/``)."""
import json
import os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import bench_tiny  # noqa: E402,F401
from benchmarks.chip import catalog, flops, xtrace  # noqa: E402

DATA = Path(__file__).resolve().parents[1] / "testdata"
V5E = catalog.load_json("peaks.json")["TPU v5 lite"]


def test_op_names_from_hlo_text():
    assert xtrace.op_name(
        "%flash_attention_pallas.19 = bf16[42,4096,64]{2,1,0:T(8,128)(2,1)} "
        "custom-call(bf16[42,4096,64]{2,1,0} %bitcast.512)") == \
        ("flash_attention_pallas.19", "custom-call")
    assert xtrace.op_name(
        "%while.16 = (s32[]{:T(128)}, /*index=5*/bf16[24,896]{1,0:T(8,128)(2,1)})"
        " while((s32[]{:T(128)}) %tuple.262), condition=%c") == ("while.16", "while")


def test_union_gaps_and_uncovered():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45), (60, 61)]
    assert xtrace.union(iv) == [[0, 20], [30, 45], [60, 61]]
    assert xtrace.length(iv) == 36
    assert xtrace.gaps(iv, 0, 70) == [(20, 30), (45, 60), (61, 70)]
    # collectives at [0,10] and [30,50]; compute covers [5,8] and [35,60]
    assert xtrace.uncovered([(0, 10), (30, 50)], [(5, 8), (35, 60)]) == 7 + 5
    assert xtrace.uncovered([(0, 10)], []) == 10
    assert xtrace.uncovered([(0, 10)], [(-5, 20)]) == 0


def test_device_summary_by_hand():
    rec = dict(window=[0, 100], host=[], devices={
        "0": dict(ops=[["while.1", "while", 0, 90], ["fusion.1", "fusion", 0, 40],
                       ["all-gather-start.2", "all-gather-start", 30, 32],
                       ["all-gather-done.2", "all-gather-done", 60, 70],
                       ["fusion.3", "fusion", 80, 90]],
                  async_=[["all-gather-start.2", "all-gather-start", 30, 70]]),
        "1": dict(ops=[["fusion.1", "fusion", 0, 50],
                       ["all-reduce.4", "all-reduce", 50, 100]])})
    rec["devices"]["0"]["async"] = rec["devices"]["0"].pop("async_")
    s = xtrace.device_summary(rec)
    assert s["window_ns"] == 100
    c0, c1 = s["chips"]["0"], s["chips"]["1"]
    # chip 0: the loop's own event counts for busy time, not as compute
    # covering the gather, which runs 30-70 and is exposed for 40-70
    assert c0["busy_ns"] == 90 and c1["busy_ns"] == 100
    assert c0["collective_ns"] == 40 and c0["exposed_ns"] == 30
    assert c1["collective_ns"] == 50 and c1["exposed_ns"] == 50
    idle = catalog.metric_reader("device.idle_share")(dict(devices=s))
    assert idle == pytest.approx(5.0)
    ctx = dict(devices=s, n_steps=2)
    assert catalog.metric_reader("collective.ms_per_step")(ctx) == \
        pytest.approx(45 / 2 * 1e-6)
    top = xtrace.breakdown(rec)["device_ops"]
    assert top[0] == ["fusion.1", pytest.approx(90e-9)]
    assert "while.1" not in [n for n, _ in top]
    assert catalog.metric_reader("collective.exposed_ms_per_step")(ctx) == \
        pytest.approx(40 / 2 * 1e-6)


def test_kernel_join_on_the_compiled_step():
    """Each kernel call of the compiled qwen2-0.5b step, priced from its
    HLO shapes; a trace event counts by its instruction name."""
    calls = xtrace.custom_calls(
        (DATA / "qwen2-0.5b.zero_topo.s4096.custom_calls.hlo").read_text())
    names = sorted(calls)
    dq = [n for n in names if n.startswith("dequant_matmul_flat_pallas")]
    fa = [n for n in names if n.startswith("flash_attention_pallas")]
    assert len(dq) == 20 and len(fa) == 2
    ops, res = calls["flash_attention_pallas.18"]
    assert ops == [("bf16", (42, 4096, 64))] * 3 and res == ("bf16", (42, 4096, 64))
    # one event per call, each taking 1 ms, inside the window
    rec = dict(window=[0, 1e9], host=[], devices={"0": dict(ops=[
        [n, "custom-call", i * 2e6, i * 2e6 + 1e6]
        for i, n in enumerate(dq + fa + ["fusion.9"])])})
    k = xtrace.kernel_summary(rec, calls, catalog.kernels(), V5E)
    assert k["dequant_matmul"]["calls"] == 20
    assert k["flash_attention_fwd"]["calls"] == 2
    f, b = flops.matmul(*calls["dequant_matmul_flat_pallas.196"])
    assert f == 2 * 12288 * 896 * 4864
    least = max(f / V5E["bf16_flops_per_s"], b / V5E["hbm_bytes_per_s"])
    assert least < 1e-3      # so a 1 ms call reads under 100% of its roofline
    share = catalog.metric_reader("dequant_matmul_roofline")(dict(kernels=k))
    assert 0 < share < 100


def test_kernel_work_of_every_call_as_before():
    """Each kernel call of the compiled qwen2-0.5b step is priced as before
    kernels could come from their own files: a matmul at 2 M C O, attention
    at 4 BH pairs D with its query's head size for both matmuls."""
    calls = xtrace.custom_calls(
        (DATA / "qwen2-0.5b.zero_topo.s4096.custom_calls.hlo").read_text())
    table = catalog.kernels()
    priced = 0
    for name, (ops, res) in calls.items():
        prefix = xtrace.kernel_of(name, table)
        if prefix is None:          # a kernel with no roofline metric
            continue
        priced += 1
        nb = sum(flops.nbytes(o) for o in ops) + flops.nbytes(res)
        if prefix == "flash_attention_pallas":
            bh, s, d = ops[0][1]
            want = 4 * bh * s * (ops[1][1][1] + 1) / 2 * d
        else:
            (_, x), (_, out) = ops[0], res
            want = 2 * x[0] * x[1] * out[-1]
        assert table[prefix]["work"](ops, res) == (want, nb), name
    assert priced == 22


def test_reduction_on_a_trace_from_the_chip():
    """30 ms of a traced window of qwen2-0.5b.zero_topo.s4096 on one v5e
    (``xtrace.load`` of the profiler's trace, cut to a layer's attention
    forward and the matmuls around it)."""
    rec = json.loads((DATA / "qwen2-0.5b.zero_topo.s4096.trace.json").read_text())
    calls = xtrace.custom_calls(
        (DATA / "qwen2-0.5b.zero_topo.s4096.custom_calls.hlo").read_text())
    s = xtrace.device_summary(rec)
    chip = s["chips"]["0"]
    assert s["window_ns"] == 30e6
    assert 0.9 * 30e6 < chip["busy_ns"] <= 30e6
    assert chip["collective_ns"] == 0           # one chip: no collective
    k = xtrace.kernel_summary(rec, calls, catalog.kernels(), V5E)
    fa = k["flash_attention_fwd"]
    assert fa["calls"] == 1
    # the call's work by hand: q, k, v, out (42, 4096, 64) bf16, causal
    least = max(4 * 42 * 64 * 4096 * 4097 / 2 / 197e12, 4 * 42 * 4096 * 64 * 2 / 819e9)
    assert fa["least_s"] == pytest.approx(least)
    assert fa["time_s"] == pytest.approx(16.878979e-3, rel=1e-6)
    share = catalog.metric_reader("flash_attention_fwd_roofline")(dict(kernels=k))
    assert share == pytest.approx(100 * least / 16.878979e-3)
    assert k["dequant_matmul"]["calls"] >= 4
    assert 0 < catalog.metric_reader("dequant_matmul_roofline")(dict(kernels=k)) < 100
    top = xtrace.breakdown(rec)
    assert top["device_ops"][0][0] == "flash_attention_pallas.19"


def test_collectives_on_a_trace_from_four_chips():
    """20 ms of a traced window of gpt-neox-20b.zero3.2x2.s2048 on a 2x2
    v5e host, from the first weight all-gather: the gathers are synchronous
    there, so all of their time is exposed."""
    rec = json.loads((DATA / "gpt-neox-20b.zero3.2x2.s2048.trace.json").read_text())
    s = xtrace.device_summary(rec)
    assert sorted(s["chips"]) == ["0", "1", "2", "3"]
    for chip, c in s["chips"].items():
        ops = rec["devices"][chip]["ops"]
        coll = [(a, b) for n, op, a, b in ops if xtrace.is_collective(n, op)]
        rest = [(a, b) for n, op, a, b in ops
                if not xtrace.is_collective(n, op) and op not in xtrace.CONTAINER]
        assert len(coll) == 12
        # by brute force: no other op overlaps a gather, so exposed == total
        assert not any(a < d and c0 < b for a, b in coll for c0, d in rest)
        lo, hi = rec["window"]
        total = sum(min(b, hi) - max(a, lo) for a, b in coll if b > lo and a < hi)
        assert c["collective_ns"] == pytest.approx(total)
        assert c["exposed_ns"] == pytest.approx(total)
        assert total < c["busy_ns"] <= s["window_ns"] == 20e6
