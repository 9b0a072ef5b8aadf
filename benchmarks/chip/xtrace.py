"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reduces the JAX profiler's ``.xplane.pb`` to a small record: each
chip's device operations (name, start, end), the host's events (for naming
what the host did during a device gap) and the benchmark's window span.
The functions after it work on that record alone, so the tests check them
on a record taken from a chip (``testdata/``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
# ops that contain other ops of the same line (a loop's event spans its body)
CONTAINER = {"while", "conditional", "call"}
WINDOW_SPAN = "bench.window"
# "%name = <result type> opcode(operands), attributes"
_OP = re.compile(r"^%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return found[-1]


def op_name(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of a device event, whose name is the
    instruction's HLO text."""
    m = _OP.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


def load(path: str) -> dict:
    """Record of one trace: per chip, ``ops`` [[instruction, opcode,
    start_ns, end_ns]] of its "XLA Ops" line and ``async`` the same of its
    "Async XLA Ops" line (an asynchronous op from its start to its done);
    ``host`` [[name, start_ns, end_ns]] of the host's threads; ``window``
    [start_ns, end_ns] of the span ``bench.window``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices[m.group(1)] = {
                key: sorted(([*op_name(e.name), e.start_ns, e.end_ns]
                             for e in lines[line].events), key=lambda o: o[2])
                if line in lines else []
                for key, line in (("ops", "XLA Ops"), ("async", "Async XLA Ops"))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = [e.start_ns, e.end_ns]
                    host.append([e.name, e.start_ns, e.end_ns])
    return dict(devices=devices, host=host, window=window)


def is_collective(name: str, opcode: str) -> bool:
    return bool(COLLECTIVE.match(opcode) or COLLECTIVE.match(name))


def union(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def uncovered(a, b) -> float:
    """Length of the union of ``a`` that no interval of ``b`` covers."""
    ua, ub = union(a), union(b)
    total, j = 0.0, 0
    for s, e in ua:
        cur = s
        while j < len(ub) and ub[j][1] <= cur:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < e:
            if ub[k][0] > cur:
                total += ub[k][0] - cur
            cur = max(cur, ub[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    out, cur = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def window_of(rec: dict) -> tuple[float, float]:
    """The traced window: the benchmark's span, where the device clock
    agrees with it; else the first to the last device operation."""
    ops = [o for d in rec["devices"].values() for o in d["ops"]]
    if not ops:
        raise ValueError("the trace holds no device operation")
    first, last = min(o[2] for o in ops), max(o[3] for o in ops)
    w = rec.get("window")
    if w and w[0] < last and w[1] > first:
        return float(w[0]), float(w[1])
    return float(first), float(last)


def device_summary(rec: dict) -> dict:
    """Per chip, in the window: busy time (the union of its operations),
    collective time (the union of its collective operations, synchronous or
    from an asynchronous start to its done), and the part of that during
    which no other operation ran. Ops that contain others (a loop) count
    for busy time only."""
    lo, hi = window_of(rec)
    out = {}
    for chip, d in rec["devices"].items():
        ops = d["ops"]
        coll = [(s, e) for n, op, s, e in ops + d.get("async", [])
                if is_collective(n, op)]
        rest = [(s, e) for n, op, s, e in ops
                if not is_collective(n, op) and op not in CONTAINER]
        out[chip] = dict(
            busy_ns=length(clip([(s, e) for *_, s, e in ops], lo, hi)),
            collective_ns=length(clip(coll, lo, hi)),
            exposed_ns=uncovered(clip(coll, lo, hi), clip(rest, lo, hi)))
    return dict(window_ns=hi - lo, chips=out)


_SHAPE = re.compile(r"([a-z]+[0-9a-z]*)\[([0-9,]*)\]")
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) custom-call\(.*"
                   r"custom_call_target=\"tpu_custom_call\"")
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}"
                       r"(?:, [a-z_]+=|$)")


def _shapes(text: str):
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _SHAPE.findall(text)]


def custom_calls(hlo_text: str) -> dict:
    """Every Pallas kernel call of a compiled program: instruction name ->
    (operand shapes, result shape)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _CALL.match(line)
        if not m:
            continue
        ops = _OPERANDS.search(line)
        res = _shapes(m.group(2))
        if ops and res:
            out[m.group(1)] = (_shapes(ops.group(1)), res[0])
    return out


def kernel_summary(rec: dict, calls: dict, table: dict, peaks: dict,
                   work: dict) -> dict:
    """Per kernel metric: device time of all its calls inside the window, and
    the least time the chip could take for the same work, the larger of
    operations over the bf16 peak and bytes over HBM bandwidth."""
    lo, hi = window_of(rec)
    out = defaultdict(lambda: dict(time_s=0.0, least_s=0.0, calls=0,
                                   flops=0.0, bytes=0.0))
    for d in rec["devices"].values():
        for name, _, s, e in d["ops"]:
            if s < lo or e > hi or name not in calls:   # whole calls only
                continue
            prefix = next((p for p in table if name.startswith(p)), None)
            if prefix is None:
                continue
            entry = table[prefix]
            flops, nb = work[entry["work"]](*calls[name])
            k = out[entry["metric"]]
            k["time_s"] += (e - s) * 1e-9
            k["least_s"] += max(flops / peaks["bf16_flops_per_s"],
                                nb / peaks["hbm_bytes_per_s"])
            k["calls"] += 1
            k["flops"] += flops
            k["bytes"] += nb
    return dict(out)


def breakdown(rec: dict, top: int = 10) -> dict:
    """The device operations that took most time (summed over chips, by
    instruction; a loop's own event is left out, its body's ops count), and
    the longest device gaps, each named after the innermost host event that
    covered it."""
    lo, hi = window_of(rec)
    by_op: dict[str, float] = defaultdict(float)
    all_gaps = []
    for d in rec["devices"].values():
        for name, op, s, e in d["ops"]:
            if op not in CONTAINER and e > lo and s < hi:
                by_op[name] += (min(e, hi) - max(s, lo)) * 1e-9
        all_gaps += gaps([(s, e) for *_, s, e in d["ops"]], lo, hi)
    host = [h for h in rec["host"] if h[0] != WINDOW_SPAN]
    named = []
    for s, e in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        cover = [h for h in host if h[1] <= mid <= h[2]]
        what = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "no host event"
        named.append([f"idle during {what}", (e - s) * 1e-9])
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return dict(device_ops=[[n, t] for n, t in ops_top], idle_gaps=named)
