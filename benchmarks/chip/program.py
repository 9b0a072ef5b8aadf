"""The system under test, built the way ``repro.launch.train.main`` builds
it: the live devices' mesh (``make_device_mesh``), the scheme's preset
(``scheme_config``), the compiled Pallas kernels on a TPU, ``TrainHparams``
and a ``ZeroEngine``, driven by ``Trainer.run``. The benchmark passes its
own seeded weights and tokens; from the program it takes nothing else."""
from __future__ import annotations

import dataclasses
import typing

import jax

from .catalog import CellError

# configuration key (as the model's published config names it) -> the
# program's ArchConfig field
ARCH_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "rotary_emb_base": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def arch_for(config: dict):
    """The registry's architecture with the configuration's sizes applied
    (the same values unless a size was cut, as ``reduced`` lists), then its
    ``program`` block: ``ArchConfig`` field paths, dotted into nested
    groups (``"moe.n_experts"``), to the values the program runs where it
    departs from the registry. A field that a published key sets is refused
    there: its cut shows in the published key and ``reduced``.

    A depth cut of a patterned model keeps the registry pattern's first
    ``n_layers`` kinds, so leading dense layers stay in place; it is refused
    where it drops a kind of layer. ``program.block_pattern`` overrides it."""
    from repro.models.registry import get_arch
    base = get_arch(config["program_arch"])
    over = {f: config[k] for k, f in ARCH_FIELDS.items() if k in config}
    if "num_key_value_heads" not in config:  # one key/value head per query head
        over["n_kv_heads"] = over.get("n_heads", base.n_heads)
    arch = dataclasses.replace(base, **over)
    block = config.get("program", {})
    for path, value in block.items():
        keys = [k for k, f in ARCH_FIELDS.items() if f == path]
        if keys:
            raise CellError(f"program.{path}: set by a published key "
                            f"({' or '.join(keys)}), not by the block")
        arch = _set(arch, path.split("."), value, path)
    n = arch.n_layers
    if base.block_pattern and n != base.n_layers and "block_pattern" not in block:
        kinds = set(base.block_pattern)
        if len(kinds) == 1:
            pattern = (base.block_pattern[0],) * n
        elif n > base.n_layers:
            raise CellError(f"{base.name}'s pattern has {base.n_layers} layers, "
                            f"not {n}; set program.block_pattern")
        else:
            pattern = base.block_pattern[:n]
            lost = sorted(kinds - set(pattern))
            if lost:
                raise CellError(f"the first {n} layers of {base.name} drop the "
                                f"kinds {lost}; keep a whole period")
        arch = dataclasses.replace(arch, block_pattern=pattern)
    if arch.block_pattern and len(arch.block_pattern) != n:
        raise CellError(f"block_pattern has {len(arch.block_pattern)} kinds "
                        f"for {n} layers")
    return arch


def _set(obj, keys: list[str], value, path: str):
    """``obj`` with the field at ``keys`` set to ``value`` (a list as a
    tuple), through ``dataclasses.replace`` at each level; a group the
    registry leaves unset (``None``) starts from its type's defaults."""
    name = keys[0]
    if name not in {f.name for f in dataclasses.fields(obj)}:
        raise CellError(f"program.{path}: {type(obj).__name__} has no field "
                        f"{name!r}")
    if len(keys) == 1:
        value = tuple(value) if isinstance(value, list) else value
    else:
        inner = getattr(obj, name)
        if inner is None:
            hint = typing.get_type_hints(type(obj))[name]
            inner = next((t() for t in typing.get_args(hint)
                          if dataclasses.is_dataclass(t)), None)
        if not dataclasses.is_dataclass(inner):
            raise CellError(f"program.{path}: {name!r} is not a group of fields")
        value = _set(inner, keys[1:], value, path)
    return dataclasses.replace(obj, **{name: value})


def hparams(traffic: dict):
    from repro.core.engine import TrainHparams
    hp = dict(traffic["hparams"])
    hp["betas"] = tuple(hp["betas"])
    return TrainHparams(**hp)


@dataclasses.dataclass
class Program:
    model: object
    engine: object
    mesh: object
    shape: object


def build(config: dict, traffic: dict, devices) -> Program:
    from repro.core.engine import ZeroEngine
    from repro.kernels import ops
    from repro.launch.mesh import make_device_mesh, scheme_config
    from repro.models.config import ShapeConfig
    from repro.models.registry import build_model

    impl = "pallas" if jax.default_backend() == "tpu" else None
    if impl:
        ops.set_default_impl(impl)
    mesh = make_device_mesh(devices)
    model = build_model(arch_for(config))
    shape = ShapeConfig("bench", traffic["seq_len"], traffic["global_batch"],
                        "train")
    zc = scheme_config(traffic["scheme"], mesh,
                       quant_block=traffic["quant_block"], impl=impl)
    eng = ZeroEngine(model.leaf_specs(), zc, mesh, hparams(traffic))
    return Program(model, eng, mesh, shape)


def trainer(prog: Program, data):
    from repro.train.trainer import Trainer
    return Trainer(prog.model, prog.engine, prog.mesh, prog.shape, data=data)
