"""Find every piece of a cell by its name, so that a later change adds a
file and edits none.

- ``BENCHMARK.json`` (checkout root): the cell's configuration and traffic names;
- ``configs/<config>.json``: the model configuration as it is run;
- ``traffic/<traffic>.json``: the job (scheme, shapes, token mix, optimizer);
- ``limits/<cell>.json``: the limit of each number that decides ``correct``;
- ``metrics/<metric>.py``: a per-layer reader, ``read(ctx) -> float | None``;
- ``reference/<family>.py``: the plain float32 model a configuration names,
  and, where it defines ``flops_per_token(cfg, table, seq_len)``, the
  family's model FLOPs a token;
- ``kernels/<prefix>.json``: a Pallas kernel whose roofline is read, by the
  prefix of its HLO instruction name (the jitted wrapper's name in the
  program's ``kernels/*.py``), with its metric and the work function that
  prices one call: a name in ``flops.WORK``, or ``<module>.py`` under
  ``kernels/`` defining ``work(operands, result)``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from . import flops

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class CellError(Exception):
    """The cell, or one of its files, is missing or malformed."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise CellError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise CellError(f"missing {path}")
    return json.loads(path.read_text())


def _module(path: Path, name: str):
    if not path.is_file():
        raise CellError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, bench: dict, base: Path = HERE, root: Path = ROOT) -> dict:
    """Everything a run of workload ``name`` needs, read from its files."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise CellError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    confs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in confs:
        raise CellError(f"workload {name} names unknown config {w['config']!r}")
    entry = confs[w["config"]]
    config = _json(root / entry["file"])
    unlisted = sorted(set(config.get("program", {})) - set(entry["reduced"]))
    if unlisted:
        raise CellError(f"config {entry['name']}: the program block's "
                        f"{unlisted} are not in its reduced list")
    metrics = [m for m in bench["per_layer"]
               if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return dict(
        name=name, chips=int(w["chips"]),
        config=config, config_name=entry["name"],
        traffic=_json(base / "traffic" / f"{w['traffic']}.json"),
        limits=_json(base / "limits" / f"{name}.json"),
        per_layer=metrics, end_to_end=end_to_end)


def metric_reader(name: str, base: Path = HERE):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    return _module(base / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_")).read


def reference(family: str):
    """The plain reference module of a model family (``reference/<family>.py``)."""
    path = HERE / "reference" / f"{family}.py"
    if not path.is_file():
        raise CellError(f"missing {path}")
    return importlib.import_module(f"{__package__}.reference.{family}")


def load_json(name: str, base: Path = HERE) -> dict:
    return _json(base / name)


def kernels(base: Path = HERE) -> dict:
    """Every kernel entry, one a file ``kernels/<prefix>.json``:
    prefix -> {"metric": name, "work": function}."""
    out = {}
    for f in sorted((base / "kernels").glob("*.json")):
        e = _json(f)
        work = e["work"]
        if work.endswith(".py"):
            fn = _module(base / "kernels" / work,
                         "bench_kernel_" + work[:-3].replace(".", "_")).work
        elif work in flops.WORK:
            fn = flops.WORK[work]
        else:
            raise CellError(f"kernel {f.stem!r}: unknown work {work!r}")
        out[f.stem] = dict(metric=e["metric"], work=fn)
    return out
