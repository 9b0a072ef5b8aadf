"""Observability layer (DESIGN.md §10): metrics JSONL schema, TFLOPS
accounting, span recorder + Chrome export, heartbeat classification,
TrainLog aggregates, and the calibration math (phase_breakdown inversion,
calibrated-Topology round-trip).

The device-level half — phased-step bitwise equivalence, span/wall
coverage, the tag-census identity on the 8-device mesh — lives in
tests/_scenarios.py::obs_trace_equivalence (run via test_distributed.py);
the multi-process straggler detection in tests/test_multiprocess.py.
"""
import json
import time

import pytest

from repro.obs import heartbeat as hb
from repro.obs import metrics as om
from repro.obs import spans


def _rec(step, rank=0, **over):
    rec = dict(step=step, rank=rank, loss=2.0 - 0.1 * step, grad_norm=1.0,
               lr=1e-3, tokens=1024.0, dt_s=0.5 if step else 10.0,
               tokens_per_s=2048.0 if step else 102.4,
               tflops_per_gpu=0.5 if step else 0.025,
               phase_ms={"fwd_allgather": 1.5, "compute": 40.0},
               overlap_efficiency=0.6, memory_hw_bytes=0,
               memory_pred_bytes=123456)
    rec.update(over)
    return rec


# -- metrics stream ----------------------------------------------------------

def test_metrics_roundtrip(tmp_path):
    """Writer -> JSONL -> reader preserves every field of every record."""
    path = tmp_path / "metrics.jsonl"
    w = om.MetricsWriter(path)
    written = [w.write(_rec(i)) for i in range(3)]
    w.close()
    assert om.read_jsonl(path) == written
    assert om.read_lanes(path) == written          # stem-only, no lanes


def test_metrics_schema_enforced(tmp_path):
    """A record missing a required field is rejected at write AND read."""
    w = om.MetricsWriter(tmp_path / "m.jsonl")
    bad = _rec(0)
    del bad["tflops_per_gpu"]
    with pytest.raises(ValueError, match="tflops_per_gpu"):
        w.write(bad)
    w.close()
    (tmp_path / "broken.jsonl").write_text(json.dumps({"step": 0}) + "\n")
    with pytest.raises(ValueError, match="missing fields"):
        om.read_jsonl(tmp_path / "broken.jsonl")


def test_metrics_rank_lanes(tmp_path):
    """Multi-process runs write per-rank lane files; read_lanes merges them
    sorted by (step, rank)."""
    stem = tmp_path / "metrics.jsonl"
    assert om.lane_path(stem, 0, 1) == stem
    assert om.lane_path(stem, 1, 2).name == "metrics.rank1.jsonl"
    for rank in (1, 0):
        w = om.MetricsWriter(stem, rank=rank, n_ranks=2)
        assert w.path != stem
        for i in range(2):
            w.write(_rec(i, rank=rank))
        w.close()
    merged = om.read_lanes(stem)
    assert [(r["step"], r["rank"]) for r in merged] == \
        [(0, 0), (0, 1), (1, 0), (1, 1)]


def test_metrics_aggregates_exclude_compile_step():
    """The first step's dt contains trace+compile time: throughput and dt
    means must exclude it, while loss/gnorm means keep all steps."""
    recs = [_rec(i) for i in range(4)]
    agg = om.aggregates(recs)
    assert agg["n_steps"] == 4 and agg["n_timed_steps"] == 3
    assert agg["dt_s_mean"] == 0.5                  # not (10 + 3*0.5)/4
    assert agg["tokens_per_s_mean"] == 2048.0
    assert agg["loss_mean"] == pytest.approx(sum(2.0 - 0.1 * i
                                                 for i in range(4)) / 4)
    one = om.aggregates(recs[:1])                   # 1-step run keeps its sample
    assert one["dt_s_mean"] == 10.0
    assert om.aggregates([]) == {}


def test_last_phase_ms():
    recs = [_rec(0), _rec(1, phase_ms={"grad_rs_w": 3.25}),
            _rec(2, phase_ms={})]
    assert om.last_phase_ms(recs) == {"grad_rs_w": 3.25}
    assert om.last_phase_ms([_rec(0, phase_ms={})]) == {}


# -- TFLOPS accounting -------------------------------------------------------

def test_tflops_formula_matches_cost_model():
    """One 6·N FLOPs-per-token convention across the repo: the runtime
    accounting (obs.metrics, what the Trainer logs) must equal
    topo.cost.tflops_per_device (what benchmarks/scaling_model.py prints)
    when fed the model's own step time."""
    from repro.topo.cost import Workload, step_cost, tflops_per_device
    from repro.topo.model import frontier
    from repro.topo.planner import preset_on_topology

    topo = frontier(8)
    cfg = preset_on_topology("zero_topo", topo)
    wl = Workload(psi=1e9, n_layers=16)
    dt = step_cost(cfg, topo, wl).step_s(wl.hidden_fraction)
    n_dev = 8 * 8
    global_tokens = wl.n_microbatch * wl.tokens_per_device_mb * n_dev
    assert om.tflops_per_gpu(int(wl.psi), global_tokens, dt, n_dev) == \
        pytest.approx(tflops_per_device(cfg, topo, wl), rel=1e-12)
    assert om.model_flops_per_token(7) == 42.0
    assert om.tflops_per_gpu(1, 1.0, 0.0, 8) == 0.0    # degenerate dt


def test_trainlog_aggregates_exclude_compile_step():
    from repro.train.trainer import TrainLog
    log = TrainLog()
    for i, dt in enumerate([10.0, 0.5, 0.5]):
        log.record(i, dict(loss=2.0, grad_norm=1.0, lr=1e-3, tokens=512.0),
                   dt, tokens_per_s=512.0 / dt, tflops_per_gpu=1.0 / dt)
    agg = log.aggregates()
    assert agg["n_steps"] == 3 and agg["n_timed_steps"] == 2
    assert agg["dt_s_mean"] == 0.5
    assert agg["tokens_per_s_mean"] == 1024.0
    assert log.lrs == [1e-3] * 3 and log.tokens == [512.0] * 3


# -- spans -------------------------------------------------------------------

def _tiny_trainer(seq=16, batch=2, data=None):
    import jax

    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.launch.mesh import make_test_mesh, scheme_config
    from repro.models.config import ShapeConfig
    from repro.models.registry import build_model, get_arch
    from repro.train.trainer import Trainer
    mesh = make_test_mesh(shape=(1, 1, 1), axes=("data", "node", "gcd"))
    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=64, vocab=128)
    model = build_model(arch)
    cfg = scheme_config("zero_topo", mesh, quant_block=64,
                        compute_dtype="float32")
    eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                     TrainHparams(lr=1e-3, total_steps=8, warmup_steps=0))
    tr = Trainer(model, eng, mesh, ShapeConfig("t", seq, batch, "train"),
                 data=data)
    return tr, eng.init_state(jax.random.key(0))


def _op_names(hlo: str) -> list[str]:
    import re
    return re.findall(r'op_name="([^"]*)"', hlo)


def test_step_scopes_change_metadata_only(monkeypatch):
    """The step's phase scopes reach every op's ``op_name`` (the backward
    as ``transpose(...)`` inside ``fwd_bwd``), and change nothing else: the
    compiled program without its metadata is the same text, and two steps
    give bitwise the same losses and master weights as a step built with
    every scope a null context."""
    import contextlib
    import re

    import numpy as np

    def build_and_run():
        tr, state = _tiny_trainer()
        batch = tr._shard_batch(tr.data.batch(0))
        hlo = tr.step_fn.lower(state, batch).compile().as_text()
        losses = []
        for _ in range(2):
            state, m = tr.step_fn(state, batch)
            losses.append(float(m["loss"]))
        master = {n: np.asarray(v) for n, v in state["master"].items()}
        return hlo, losses, master

    hlo, losses, master = build_and_run()
    with monkeypatch.context() as mp:
        mp.setattr(spans, "scope", lambda name: contextlib.nullcontext())
        hlo0, losses0, master0 = build_and_run()
    names = _op_names(hlo)
    parts = {p for n in names for p in n.split("/")}
    assert {"fwd_bwd", "gnorm_clip", "update"} <= parts
    fwd_bwd = [n.split("/") for n in names if "fwd_bwd" in n.split("/")]
    assert any(any(p.startswith("transpose(") for p in n) for n in fwd_bwd)
    assert any(not any(p.startswith("transpose(") for p in n) for n in fwd_bwd)
    assert not {"fwd_bwd", "gnorm_clip", "update"} & {
        p for n in _op_names(hlo0) for p in n.split("/")}

    def strip(text):            # metadata, and the source-location tables
        text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)"
                      r"\n.*?\n\n", "", text, flags=re.S | re.M)
        return re.sub(r", metadata=\{[^}]*\}", "", text)
    assert strip(hlo) == strip(hlo0)
    assert losses == losses0
    for n in master:
        np.testing.assert_array_equal(master[n], master0[n], err_msg=n)


def test_trainer_spans_in_a_profiler_trace(tmp_path):
    """Under ``jax.profiler.trace`` every loop phase of ``Trainer.run`` is a
    ``train.*`` span nested in its step's ``train`` step marker, all
    carrying the step number that ``TrainLog.steps`` records."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr, state = _tiny_trainer()
    state = tr.run(state, 1, log_every=0)        # compile outside the trace
    with jax.profiler.trace(str(tmp_path / "tr")):
        tr.run(state, 2, log_every=1, ckpt_dir=str(tmp_path / "ck"),
               ckpt_every=2, print_fn=lambda *a: None)
    path = glob.glob(str(tmp_path / "tr" / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    steps, kids = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if e.name == "train":
                    steps[st["step_num"]] = (e.start_ns, e.end_ns)
                elif e.name.startswith("train."):
                    kids.append((e.name, st["step"], e.start_ns, e.end_ns))
    assert sorted(steps) == tr.log.steps[1:] == [2, 3]
    loop = ("train.data", "train.shard", "train.dispatch", "train.wait",
            "train.fetch", "train.log")
    for k, (lo, hi) in steps.items():
        mine = [(n, s, e) for n, step, s, e in kids if step == k]
        assert all(lo <= s <= e <= hi for _, s, e in mine)
        names = [n for n, _, _ in sorted(mine, key=lambda m: m[1])]
        assert names == list(loop) + (["train.ckpt"] if k == 3 else [])


def test_compile_counter_per_step():
    """A run's first step compiles the step and its second compiles nothing;
    a batch of a new shape recompiles, and the loop says so in one line."""
    import re

    import numpy as np

    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, 128, (2, 17), dtype=np.int32)}
               for _ in range(2)]
    batches.append({"tokens": rng.integers(0, 128, (2, 33), dtype=np.int32)})
    tr, state = _tiny_trainer(data=batches)
    said = []
    tr.run(state, 3, log_every=0, print_fn=said.append)
    assert tr.log.compiles[0] >= 1 and tr.log.compile_s[0] > 0
    assert tr.log.compiles[1] == 0
    assert tr.log.compiles[2] >= 1
    assert len(said) == 1 and re.fullmatch(
        rf"step 3: recompiled \({tr.log.compiles[2]} compilations; persistent "
        rf"cache: \d+ hits, \d+ misses; {tr.log.compile_s[2]:.2f} s of "
        rf"tracing, lowering and compiling\)", said[0]), said


_CACHED_BUILDS = """
import contextlib, sys
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
sys.path.insert(0, "tests")
import test_obs
from repro.obs import spans

def compiled():
    tr, state = test_obs._tiny_trainer()
    batch = tr._shard_batch(tr.data.batch(0))
    return tr.step_fn.lower(state, batch).compile().as_text()

real = spans.scope
spans.scope = lambda name: contextlib.nullcontext()
assert "fwd_bwd" not in compiled()
spans.scope = real
misses = spans.compile_counter().cache_misses
assert "fwd_bwd" in compiled()
assert spans.compile_counter().cache_misses > misses
print("SCOPES_KEPT")
"""


def test_persistent_cache_keeps_the_steps_scopes(tmp_path):
    """A step whose scopes differ from a cached build's is compiled afresh,
    not served the cached executable with the other build's ``op_name``
    metadata (JAX leaves metadata out of the cache key unless told)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(filter(None, (
                   str(root / "src"), os.environ.get("PYTHONPATH")))))
    out = subprocess.run(
        [sys.executable, "-c", _CACHED_BUILDS, str(tmp_path / "cache")],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert "SCOPES_KEPT" in out.stdout, out.stdout + out.stderr


def test_compile_counter_counts_nested_spans_once():
    """Compile time is the union of JAX's compile spans: a trace nested in
    another's counts once, disjoint spans add, in any order of arrival;
    executables are counted at the backend compile (or cache fetch)."""
    c = spans.CompileCounter()
    trace, lower, backend = spans.COMPILE_EVENTS
    c._span(trace, 2.0, 5.0)          # an inner jit's trace ends first
    c._span(trace, 0.0, 10.0)         # then its outer trace
    c._span(lower, 10.0, 12.0)        # touches the trace: one span 0-12
    c._span(backend, 20.0, 25.0)
    c._span(backend, 14.0, 15.0)      # arrives late, lies before the last
    c._span("/jax/other", 0.0, 100.0)
    assert c.seconds == pytest.approx(12.0 + 5.0 + 1.0)
    assert c._spans == [(0.0, 12.0), (14.0, 15.0), (20.0, 25.0)]
    assert c.count == 2
    c._event(spans.CACHE_HITS)
    c._event(spans.CACHE_MISSES)
    c._event(spans.CACHE_MISSES)
    assert (c.cache_hits, c.cache_misses) == (1, 2)
    assert spans.compile_counter() is spans.compile_counter()


def test_span_recorder_and_chrome_export(tmp_path):
    rec = spans.SpanRecorder()
    rec.step = 0
    out = rec.fenced("fwd_bwd", lambda a, b: a + b, 1, 2)
    assert out == 3
    rec.timed("fwd_allgather", 0.25)
    rec.step = 1
    rec.fenced("fwd_bwd", lambda: None)
    s0 = rec.step_seconds(0)
    assert set(s0) == {"fwd_bwd", "fwd_allgather"}
    assert s0["fwd_allgather"] == 0.25
    assert set(rec.step_seconds(1)) == {"fwd_bwd"}

    path = spans.write_chrome_trace(rec.chrome_events(rank=3),
                                    tmp_path / "trace.json")
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    assert len(evs) == 3
    assert all(e["ph"] == "X" and e["pid"] == 3 for e in evs)
    assert [e["args"]["step"] for e in evs] == [0, 0, 1]
    assert evs[1]["dur"] == pytest.approx(0.25e6)


# -- heartbeat ---------------------------------------------------------------

def test_heartbeat_classification(tmp_path):
    """dead / stalled / behind / ok from synthetic stamps, with the ``now``
    knob pinning ages deterministically."""
    hb.stamp(tmp_path, 0, 5)
    hb.stamp(tmp_path, 1, 3)
    now = time.time()
    rep = hb.straggler_report(tmp_path, 3, stall_s=60.0, now=now)
    assert rep["max_step"] == 5 and not rep["ok"]
    assert rep["ranks"][0]["status"] == "ok"
    assert rep["ranks"][1]["status"] == "behind"
    assert rep["ranks"][2]["status"] == "dead"
    assert rep["stragglers"] == [1, 2]
    # age every stamp past the stall window
    stale = hb.straggler_report(tmp_path, 2, stall_s=60.0, now=now + 120)
    assert all(v["status"] == "stalled" for v in stale["ranks"].values())
    text = hb.format_report(rep)
    assert "rank 1: behind" in text and "rank 2: dead" in text
    ok = hb.straggler_report(tmp_path, 1, stall_s=60.0, now=now)
    assert ok["ok"] and "all ranks ok" in hb.format_report(ok)


def test_heartbeat_stamp_atomic(tmp_path):
    """Stamps are tmp+rename: re-stamping leaves exactly one valid JSON."""
    for step in range(3):
        p = hb.stamp(tmp_path, 0, step)
    assert json.loads(p.read_text())["step"] == 2
    assert list(tmp_path.glob("*.tmp")) == []
    assert hb.read_stamps(tmp_path) == {0: json.loads(p.read_text())}


# -- calibration math --------------------------------------------------------

def test_solve_bandwidths_inverts_cost_model():
    """Feeding phase_breakdown's own predicted seconds back through the
    back-solve recovers each bottleneck link's preset bandwidth exactly —
    the identity that makes obs.calibrate's output trustworthy."""
    from repro.obs.calibrate import solve_bandwidths
    from repro.topo.cost import Workload, phase_breakdown
    from repro.topo.model import frontier
    from repro.topo.planner import preset_on_topology

    topo = frontier(8)
    cfg = preset_on_topology("zero_topo", topo)
    pred = phase_breakdown(cfg, topo, Workload(psi=1e9, n_layers=16))
    measured = {ph: rec["seconds"] for ph, rec in pred.items()}
    solved = solve_bandwidths(pred, measured)
    assert solved            # at least one axis solved
    for ax, bw in solved.items():
        assert bw == pytest.approx(topo.link(ax).bandwidth, rel=1e-9), ax
    # halving every wire time (latency share fixed) doubles the solved bw
    fast = solve_bandwidths(
        pred, {ph: pred[ph]["latency_s"] + (s - pred[ph]["latency_s"]) / 2
               for ph, s in measured.items()})
    for ax in solved:
        assert fast[ax] == pytest.approx(2 * solved[ax], rel=1e-9), ax


def test_calibrated_topology_roundtrip(tmp_path):
    """model.calibrated overrides only the named links; the saved JSON
    loads back through load_topology and the planner's preset mapper
    accepts it (what ``planner --topology <calibrate output>`` does)."""
    from repro.topo.model import calibrated, frontier, load_topology
    from repro.topo.planner import preset_on_topology

    topo = frontier(4)
    cal = calibrated(topo, {"node": 55e9, "bogus": 1.0, "gcd": 0.0})
    assert cal.link("node").bandwidth == 55e9
    assert cal.link("gcd").bandwidth == topo.link("gcd").bandwidth  # 0 skipped
    assert cal.link("data").bandwidth == topo.link("data").bandwidth
    assert cal.name == "frontier:calibrated"
    assert cal.link("node").latency == topo.link("node").latency

    path = tmp_path / "topo_calibrated.json"
    cal.save(path)
    loaded = load_topology(str(path))
    assert loaded.link("node").bandwidth == 55e9
    assert [l.name for l in loaded.links] == [l.name for l in topo.links]
    cfg = preset_on_topology("zero_topo", loaded)
    cfg.validate_dependency_rule()


def test_phase_breakdown_consistent_with_step_cost():
    """phase_breakdown is step_cost's own ledger: per-phase seconds match
    comm_s, exposed_s is the non-in-loop per-step share, and the streaming
    regime moves the grad phases into the loop."""
    from repro.topo.cost import (PER_STEP, PHASES, STREAMED, Workload,
                                 phase_breakdown, step_cost)
    from repro.topo.model import frontier
    from repro.topo.planner import preset_on_topology

    topo = frontier(8)
    cfg = preset_on_topology("zero_topo", topo)
    for stream in (False, True):
        wl = Workload(psi=1e9, n_layers=16, stream_grads=stream)
        pred = phase_breakdown(cfg, topo, wl)
        cost = step_cost(cfg, topo, wl)
        assert set(pred) == set(PHASES)
        for ph in PHASES:
            assert pred[ph]["seconds"] == cost.comm_s[ph], ph
        assert cost.exposed_s == pytest.approx(sum(
            pred[ph]["seconds"] for ph in PER_STEP
            if not pred[ph]["in_loop"]))
        for ph in STREAMED:
            assert pred[ph]["in_loop"] == stream, ph
