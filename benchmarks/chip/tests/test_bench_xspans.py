"""The program's names in a trace: step phases from ``op_name`` paths, loop
spans on the host's clock, device idle time inside them, and the five
readers built on them, by hand on small records and on a record taken
from the chip (``testdata/``)."""
import json
import os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import bench_tiny  # noqa: E402,F401
from benchmarks.chip import catalog, xspans, xtrace  # noqa: E402

DATA = Path(__file__).resolve().parents[1] / "testdata"
READERS = ("loop.host_idle_ms_per_step", "step.fwd_ms_per_step",
           "step.bwd_ms_per_step", "step.update_ms_per_step")


def test_step_phase_of_op_names():
    """``op_name`` paths as JAX writes them for the engine's step (scan
    body, rematerialised layer, shard_map), by phase."""
    pre = "jit(local_step)/jit(main)/shard_map"
    cases = {
        f"{pre}/fwd_bwd/jvp(mb_loss)/while/body/closed_call/dot_general": "fwd",
        f"{pre}/fwd_bwd/transpose(jvp(mb_loss))/while/body/closed_call/"
        "checkpoint/rematted_computation/dot_general": "bwd",
        f"{pre}/fwd_bwd/transpose(jvp(mb_loss))/while/body/gather/issue/mul":
            "bwd",
        f"{pre}/grad_rs_e/reduce_scatter": "grad_sync",
        f"{pre}/cross_replica/psum": "grad_sync",
        f"{pre}/gnorm_clip/sqrt": "update",
        f"{pre}/update/all_gather": "update",
        f"{pre}/dynamic_update_slice": None,
        "": None,
    }
    for path, phase in cases.items():
        assert xspans.step_phase(path) == phase, path


def _rec():
    """Two chips over a 100 ns window. Chip 0: forward 0-30 (a loop),
    backward 40-70, update 75-85; chip 1 the same, with its update 75-95.
    The loop: step 1's wait 10-72, its fetch 72-80, step 2's data 80-90
    and dispatch 90-100."""
    def ops(update_end):
        return [["while.1", "while", 0, 30, "jit(s)/fwd_bwd/jvp(f)/while"],
                ["fusion.1", "fusion", 0, 30, "jit(s)/fwd_bwd/jvp(f)/dot"],
                ["fusion.2", "fusion", 40, 60,
                 "jit(s)/fwd_bwd/transpose(jvp(f))/dot"],
                ["fusion.3", "fusion", 55, 70,
                 "jit(s)/fwd_bwd/transpose(jvp(f))/checkpoint/"
                 "rematted_computation/dot"],
                ["fusion.4", "fusion", 75, update_end, "jit(s)/update/mul"]]
    return dict(window=[0, 100],
                devices={"0": dict(ops=ops(85)), "1": dict(ops=ops(95))},
                host=[["train", 0, 80], ["train.wait", 10, 72],
                      ["train.fetch", 72, 80], ["train", 80, 100],
                      ["train.data", 80, 90], ["train.dispatch", 90, 100],
                      ["train.data", 100, 110]])


def test_device_time_and_idle_by_hand():
    rec = _rec()
    t = xspans.device_time_by(rec, xspans.step_phase)
    # the loop's own event is left out; chip means: update (10 + 20) / 2
    assert t == {"fwd": 30, "bwd": 30, "update": 15}
    loop = xspans.host_spans(rec, xspans.LOOP)
    assert [s[0] for s in loop] == ["train.wait", "train.fetch",
                                    "train.data", "train.dispatch"]
    assert len(xspans.host_spans(rec, "train")) == 6
    # idle in the fetch 72-80: chip 0 72-75, chip 1 72-75; in the data and
    # dispatch 80-100: chip 0 85-100, chip 1 95-100
    outside = [(s, e) for n, s, e in loop if n != "train.wait"]
    assert xspans.idle_within(rec, outside) == pytest.approx((3 + 15 + 3 + 5) / 2)
    # and in the wait 10-72: 30-40 on both chips, 70-72
    assert xspans.idle_within(rec, [(10, 72)]) == 12


def test_readers_by_hand(monkeypatch):
    rec = _rec()
    monkeypatch.setattr(xspans, "window_trace", lambda ctx: rec)
    ctx = dict(n_steps=2)
    got = {m: catalog.metric_reader(m)(ctx) for m in READERS}
    assert got == pytest.approx({
        "loop.host_idle_ms_per_step": 13 / 2 * 1e-6,
        "step.fwd_ms_per_step": 15e-6, "step.bwd_ms_per_step": 15e-6,
        "step.update_ms_per_step": 7.5e-6})


def test_readers_are_silent_without_the_programs_names(monkeypatch):
    """A program with no step scopes and no loop spans (an older commit
    traced under this benchmark) reads ``None`` on every reader, as does a
    run with no trace of its window."""
    rec = _rec()
    rec["host"] = [h for h in rec["host"] if not h[0].startswith("train")]
    for d in rec["devices"].values():
        for op in d["ops"]:
            op[4] = "jit(local_step)/jit(main)/dot_general"
    monkeypatch.setattr(xspans, "window_trace", lambda ctx: rec)
    assert all(catalog.metric_reader(m)(dict(n_steps=2)) is None
               for m in READERS)
    monkeypatch.setattr(xspans, "window_trace", lambda ctx: None)
    assert all(catalog.metric_reader(m)(dict(n_steps=2)) is None
               for m in READERS)


def test_window_trace_takes_only_this_runs_window(tmp_path, monkeypatch):
    """The newest trace under the trace directory is used only where its
    window is the one the harness reduced; an empty directory reads
    nothing."""
    monkeypatch.setattr(xspans, "TRACE_ROOT", tmp_path)
    ctx = dict(devices=dict(window_ns=100.0, chips={}))
    assert xspans.window_trace(ctx) is None
    older = tmp_path / "a" / "plugins" / "profile" / "1" / "h.xplane.pb"
    newer = tmp_path / "b" / "plugins" / "profile" / "2" / "h.xplane.pb"
    for i, p in enumerate((older, newer)):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
        os.utime(p, (1000 + i, 1000 + i))
    seen = []
    monkeypatch.setattr(xspans, "load", lambda path: seen.append(path) or _rec())
    assert xspans.window_trace(ctx) is not None
    assert xspans.window_trace(ctx) is not None      # loaded once
    assert seen == [str(newer)]
    ctx["devices"]["window_ns"] = 90.0
    assert xspans.window_trace(ctx) is None
    assert xspans.window_trace(dict(n_steps=1)) is None


def test_setup_compile_reads_the_programs_counter():
    import jax
    import jax.numpy as jnp

    from repro.obs import spans
    c = spans.compile_counter()
    before = c.seconds
    jax.jit(lambda x: jnp.sin(x) * 3.0 + x.shape[0])(jnp.ones(7)).block_until_ready()
    got = catalog.metric_reader("setup.compile_s")({})
    assert got == c.seconds and got > before


def _field(number: int, payload: bytes) -> bytes:
    """One length-delimited protobuf field (field numbers under 16)."""
    n, size = len(payload), bytearray()
    while n >= 0x80:
        size.append(n & 0x7F | 0x80)
        n >>= 7
    return bytes([number << 3 | 2]) + bytes(size) + bytes([n]) + payload


def test_op_names_from_the_traces_metadata(tmp_path):
    """An ``XSpace`` written by hand: a device plane whose ops' metadata
    carry ``tf_op`` stats (one op has none), beside a host plane."""
    def meta(mid, name, stats=b""):
        body = bytes([1 << 3, mid]) + _field(2, name.encode()) + stats
        return _field(4, bytes([1 << 3, mid]) + _field(2, body))
    tf_op = _field(5, bytes([1 << 3, 7]) + _field(5, b"jit(s)/fwd_bwd/dot:"))
    plane = (_field(2, b"/device:TPU:0")
             + meta(1, "%fusion.1 = f32[8]{0} fusion(%p)", tf_op)
             + meta(2, "%copy.3 = f32[8]{0} copy(%fusion.1)")
             + _field(5, bytes([1 << 3, 7]) + _field(2, bytes([1 << 3, 7])
                                                    + _field(2, b"tf_op"))))
    host = _field(2, b"/host:CPU") + meta(1, "%fusion.9 = f32[] fusion()", tf_op)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, host))
    assert xspans.op_names(str(path)) == {"0": {"fusion.1": "jit(s)/fwd_bwd/dot"}}


def test_op_names_on_metadata_from_the_chip():
    """Event metadata of the compiled qwen2-0.5b step, cut from a trace on a
    v5e: the ``op_name`` of each op agrees with the record of the same
    trace below, and the step's phases are there."""
    got = xspans.op_names(str(DATA / "qwen2-0.5b.zero_topo.s4096.op_names.xplane.pb"))
    rec = json.loads((DATA / "qwen2-0.5b.zero_topo.s4096.spans.json").read_text())
    known = {o[0]: o[4] for o in rec["devices"]["0"]["ops"]}
    assert len(got["0"]) >= 10
    assert all(known[k] == v for k, v in got["0"].items())
    assert got["0"]["fusion.161"] == "jit(local_step)/gnorm_clip/reduce_sum"
    assert {xspans.step_phase(v) for v in got["0"].values()} >= {
        "fwd", "bwd", "update"}


def test_reduction_on_a_trace_from_the_chip():
    """72 ms of a traced window of qwen2-0.5b.zero_topo.s4096 on one v5e
    around the boundary of two steps (``xspans.load`` of the profiler's
    trace, cut): the end of step 5's backward and its update, the loop
    between the steps, and step 6's first ops."""
    rec = json.loads((DATA / "qwen2-0.5b.zero_topo.s4096.spans.json").read_text())
    ops = rec["devices"]["0"]["ops"]
    lo, hi = rec["window"]
    t = xspans.device_time_by(rec, xspans.step_phase)
    assert set(t) == {"fwd", "bwd", "update"}
    for phase in t:
        mine = [(s, e) for _, op, s, e, p in ops
                if op not in xtrace.CONTAINER and xspans.step_phase(p) == phase]
        # by brute force: the ops of one chip do not overlap
        assert t[phase] == pytest.approx(
            sum(min(e, hi) - max(s, lo) for s, e in mine if e > lo and s < hi))
    assert 25e6 < t["update"] < 35e6          # AdamW and the INT8 requantize
    loop = xspans.host_spans(rec, xspans.LOOP)
    assert [n for n, _, _ in loop] == [
        "train.fetch", "train.data", "train.shard", "train.dispatch"]
    # idle inside the loop's spans, by brute force over the gaps between
    # the chip's busy intervals
    busy = xtrace.union([(s, e) for _, _, s, e, _ in ops])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    want = sum(max(0.0, min(b, e) - max(a, s))
               for a, b in gaps for _, s, e in loop)
    assert xspans.idle_within(rec, [(s, e) for _, s, e in loop]) == \
        pytest.approx(want)
    assert 3e6 < want < 10e6
