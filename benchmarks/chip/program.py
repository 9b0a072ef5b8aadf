"""The system under test, built the way ``repro.launch.train.main`` builds
it: the live devices' mesh (``make_device_mesh``), the scheme's preset
(``scheme_config``), the compiled Pallas kernels on a TPU, ``TrainHparams``
and a ``ZeroEngine``, driven by ``Trainer.run``. The benchmark passes its
own seeded weights and tokens; from the program it takes nothing else."""
from __future__ import annotations

import dataclasses

import jax

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
    (the same values unless a size was cut, as ``reduced`` lists)."""
    from repro.models.registry import get_arch
    base = get_arch(config["program_arch"])
    over = {f: config[k] for k, f in ARCH_FIELDS.items() if k in config}
    if "num_key_value_heads" not in config:  # one key/value head per query head
        over["n_kv_heads"] = over.get("n_heads", base.n_heads)
    if base.block_pattern and over.get("n_layers", base.n_layers) != base.n_layers:
        if len(set(base.block_pattern)) != 1:
            raise ValueError("only a uniform layer pattern can be cut in depth")
        over["block_pattern"] = (base.block_pattern[0],) * over["n_layers"]
    return dataclasses.replace(base, **over)


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
