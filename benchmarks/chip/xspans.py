"""The program's own names in a traced window: its loop spans on the host
(``train.*``, opened by ``Trainer.run``) and its step scopes on the device
(each op's ``op_name`` path: ``fwd_bwd``, ``grad_rs_e``, ``cross_replica``,
``gnorm_clip``, ``update``, opened by ``ZeroEngine``'s step).

``ctx`` holds no trace, so ``window_trace`` loads the one this run wrote
(``run.py`` traces into ``.bench_out/trace/<cell>``) and uses it only if
its window is the one ``ctx["devices"]`` was reduced from. A program
without these names leaves every reading empty and its readers return
``None``. The functions after ``load`` work on its record alone, so the
tests check them on a record taken from a chip (``testdata/``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from . import catalog, xtrace

TRACE_ROOT = catalog.ROOT / ".bench_out" / "trace"
LOOP = "train."          # obs.spans.span names of Trainer.run's phases
OP_NAME = "tf_op"        # the stat that holds an op's "<op_name>:"
_DEVICE = re.compile(r"/device:TPU:(\d+)$")
_loaded: dict = {}


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message; a
    length-delimited value comes as a slice of ``buf``."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def op_names(path: str) -> dict[str, dict[str, str]]:
    """Per chip, HLO instruction -> ``op_name``, from the trace's own event
    metadata: the profiler keeps each op's ``op_name`` in a ``tf_op`` stat,
    which ``jax.profiler.ProfileData`` does not show. Reads the
    ``XSpace`` proto (tsl/profiler/protobuf/xplane.proto: planes 1; a
    plane's name 2, event_metadata 4, stat_metadata 5; metadata id 1, name
    2, stats 5; a stat's metadata_id 1, str_value 5) and skips the rest."""
    out = {}
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], {}
        for f_no, v in _fields(plane):
            if f_no == 2:
                name = bytes(v).decode()
            elif f_no in (4, 5):                 # map entry: key 1, value 2
                value = next((x for k, x in _fields(v) if k == 2), b"")
                meta = dict(_fields(value))
                if f_no == 5:
                    stats[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
                else:
                    events.append((bytes(meta.get(2, b"")).decode(),
                                   [dict(_fields(st)) for k, st in
                                    _fields(value) if k == 5]))
        m = _DEVICE.match(name)
        if not m:
            continue
        tf_op = next((k for k, n in stats.items() if n == OP_NAME), None)
        chip = out.setdefault(m.group(1), {})
        for text, st in events:
            tf = next((bytes(s[5]).decode() for s in st
                          if s.get(1) == tf_op and 5 in s), "")
            if tf:
                chip[xtrace.op_name(text)[0]] = tf.rsplit(":", 1)[0]
    return out


def load(path: str) -> dict:
    """``xtrace.load``'s record of one trace, with each device op's
    ``op_name`` appended: [instruction, opcode, start_ns, end_ns, op_name],
    empty where the op has none."""
    rec = xtrace.load(path)
    names = op_names(path)
    for chip, d in rec["devices"].items():
        known = names.get(chip, {})
        for op in d["ops"]:
            op.append(known.get(op[0], ""))
    return rec


def window_trace(ctx: dict) -> dict | None:
    """This run's trace record, or ``None`` where there is none or the
    newest trace is not the window ``ctx`` describes. Loaded once for all
    the readers of a run."""
    dev = ctx.get("devices")
    found = glob.glob(str(TRACE_ROOT / "*" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not dev or not found:
        return None
    path = max(found, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = load(path)
    rec = _loaded[key]
    if not any(d["ops"] for d in rec["devices"].values()):
        return None
    lo, hi = xtrace.window_of(rec)
    return rec if hi - lo == dev["window_ns"] else None


def host_spans(rec: dict, prefix: str) -> list[list]:
    """[name, start_ns, end_ns] of the host events whose name starts with
    ``prefix``, inside the window."""
    lo, hi = xtrace.window_of(rec)
    return [h for h in rec["host"]
            if h[0].startswith(prefix) and h[1] >= lo and h[2] <= hi]


def idle_within(rec: dict, intervals) -> float:
    """Device time in ``intervals`` (of the host's clock) during which no
    operation ran, inside the window, mean over the chips (ns)."""
    lo, hi = xtrace.window_of(rec)
    inside = xtrace.clip(intervals, lo, hi)
    idle = [xtrace.uncovered(inside, xtrace.clip([(o[2], o[3]) for o in d["ops"]],
                                                 lo, hi))
            for d in rec["devices"].values()]
    return sum(idle) / len(idle)


def device_time_by(rec: dict, classify) -> dict[str, float]:
    """Device time inside the window of the ops that ``classify(op_name)``
    puts in each class (``None``: in none), as the union of each chip's
    intervals of the class, mean over the chips (ns). Ops that contain
    others (a loop) are left out: their body's ops count."""
    lo, hi = xtrace.window_of(rec)
    total: dict[str, float] = defaultdict(float)
    for d in rec["devices"].values():
        by = defaultdict(list)
        for _, opcode, s, e, path in d["ops"]:
            c = None if opcode in xtrace.CONTAINER else classify(path)
            if c is not None:
                by[c].append((s, e))
        for c, iv in by.items():
            total[c] += xtrace.length(xtrace.clip(iv, lo, hi))
    return {c: t / len(rec["devices"]) for c, t in total.items()}


def step_phase(op_name: str) -> str | None:
    """The step's phase of an op, from its ``op_name`` path: ``fwd`` and
    ``bwd`` inside ``fwd_bwd`` (the backward, rematerialised forward
    included, is JAX's ``transpose(...)``), ``grad_sync`` (``grad_rs_e``,
    ``cross_replica``), ``update`` (``gnorm_clip``, ``update``)."""
    parts = op_name.split("/")
    if "fwd_bwd" in parts:
        return "bwd" if any(p.startswith("transpose(") for p in parts) else "fwd"
    if "grad_rs_e" in parts or "cross_replica" in parts:
        return "grad_sync"
    if "gnorm_clip" in parts or "update" in parts:
        return "update"
    return None


def phase_ms_per_step(ctx: dict, phase: str) -> float | None:
    """Device time of one step phase per window step (ms), mean over the
    chips; ``None`` where the trace's ops carry no step scope."""
    rec = window_trace(ctx)
    if rec is None:
        return None
    t = device_time_by(rec, step_phase)
    if not t:
        return None
    return t.get(phase, 0.0) / ctx["n_steps"] * 1e-6
