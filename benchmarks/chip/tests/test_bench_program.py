"""A configuration reaches the program through its own file: the published
keys, a depth cut of the registry's layer pattern and a ``program`` block
of ``ArchConfig`` field paths, each listed in ``reduced``; and a kernel
reaches the roofline table through ``kernels/<prefix>.json``. Each from
files in a scratch directory, with no edit to the harness."""
import dataclasses
import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import bench_tiny  # noqa: E402
from benchmarks.chip import catalog, program, xtrace  # noqa: E402


def _arch_before(config: dict):
    """``arch_for`` as it was before the ``program`` block: the published
    keys alone, and depth cuts of uniform patterns only."""
    from repro.models.registry import get_arch
    base = get_arch(config["program_arch"])
    over = {f: config[k] for k, f in program.ARCH_FIELDS.items() if k in config}
    if "num_key_value_heads" not in config:
        over["n_kv_heads"] = over.get("n_heads", base.n_heads)
    if base.block_pattern and over.get("n_layers", base.n_layers) != base.n_layers:
        assert len(set(base.block_pattern)) == 1
        over["block_pattern"] = (base.block_pattern[0],) * over["n_layers"]
    return dataclasses.replace(base, **over)


@pytest.mark.parametrize("name", ["qwen2-0.5b", "gpt-neox-20b"])
def test_benchmark_configs_resolve_as_before(name):
    cfg = catalog.load_json(f"configs/{name}.json")
    got, want = program.arch_for(cfg), _arch_before(cfg)
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
        assert type(getattr(got, f.name)) is type(getattr(want, f.name)), f.name


JAMBA = {
    "source": "https://huggingface.co/ai21labs/Jamba-v0.1",
    "program_arch": "jamba-v0.1-52b", "reference": "none",
    "hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
    "num_key_value_heads": 8, "num_hidden_layers": 8, "num_experts": 16,
    "num_experts_per_tok": 2, "vocab_size": 8192,
    "tie_word_embeddings": False,
    "reduced": ["num_hidden_layers", "vocab_size", "moe.n_experts",
                "moe.capacity_factor"],
    "program": {"moe.n_experts": 4, "moe.capacity_factor": 2.0},
}


def _jamba(program: dict, **keys) -> dict:
    """JAMBA with another ``program`` block, its paths listed in ``reduced``."""
    return dict(JAMBA, program=program,
                reduced=["num_hidden_layers", "vocab_size", *program], **keys)


def _cell(tmp_path, config: dict):
    """``config`` as the file of a cell's configuration, whose entry in
    ``BENCHMARK.json`` lists the file's own ``reduced``."""
    bench, cell = bench_tiny.make(tmp_path, "qwen2-0.5b")
    bench["configs"][0]["reduced"] = config["reduced"]
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(config))
    return catalog.cell(cell, bench, tmp_path, tmp_path)["config"]


def test_patterned_config_cut_and_overridden_by_its_file(tmp_path):
    """jamba-v0.1-52b cut to one 8-layer period (the attention layer is the
    fifth) and to a slice of its vocabulary by its published keys, with the
    experts held and their capacity set by the ``program`` block."""
    from repro.models.registry import build_model, get_arch
    arch = program.arch_for(_cell(tmp_path, JAMBA))
    assert arch.block_pattern == get_arch("jamba-v0.1-52b").block_pattern[:8]
    assert arch.block_pattern[4] == "attn_mlp"
    assert (arch.moe.n_experts, arch.moe.d_ff, arch.moe.top_k) == (4, 14336, 2)
    assert arch.moe.capacity_factor == 2.0
    assert (arch.vocab, arch.d_model, arch.n_kv_heads) == (8192, 4096, 8)
    specs = build_model(arch).leaf_specs()
    assert specs["embed"].shape == (8192, 4096)
    moe = [s for n, s in specs.items() if n.startswith("mamba_moe.")
           and 4 in s.shape]
    assert moe and all(s.stack == 4 for s in moe)   # 4 of the 8 layers
    assert specs["attn_mlp.wq"].stack == 1


def test_explicit_pattern_and_unset_group(tmp_path):
    """``program.block_pattern`` replaces the cut's prefix; a group the
    registry leaves unset starts from its defaults."""
    cfg = _jamba({"block_pattern": ["attn_mlp", "mamba_moe"], "mla.kv_lora": 512},
                 num_hidden_layers=2)
    arch = program.arch_for(_cell(tmp_path, cfg))
    assert arch.block_pattern == ("attn_mlp", "mamba_moe")
    assert arch.mla.kv_lora == 512 and arch.mla.v_head == 64


@pytest.mark.parametrize("config, names", [
    (dict(JAMBA, num_hidden_layers=4), "attn_mlp"),
    (dict(JAMBA, num_hidden_layers=40), "set program.block_pattern"),
    (_jamba({"moe.experts_held": 8}), "experts_held"),
    (_jamba({"window": 512}), "window"),
    (_jamba({"vocab.size": 1}), "vocab"),
    (_jamba({"block_pattern": ["attn_mlp"]}), "1 kinds for 8 layers"),
    (_jamba({"vocab": 65536}), r"program.vocab: set by a published key \(vocab_size\)"),
    (_jamba({"n_layers": 4}), r"published key \(num_hidden_layers\)"),
    (dict(JAMBA, reduced=["num_hidden_layers", "vocab_size"]),
     r"\['moe.capacity_factor', 'moe.n_experts'\] are not in its reduced"),
], ids=["cut_drops_attention", "deeper_than_pattern", "unknown_nested_field",
        "unknown_field", "not_a_group", "pattern_length", "published_width",
        "published_depth", "block_not_in_reduced"])
def test_refused_with_the_field_named(tmp_path, config, names):
    with pytest.raises(catalog.CellError, match=names):
        program.arch_for(_cell(tmp_path, config))


WORK = '''
def work(operands, result):
    (_, x), = operands[:1]
    return 3.0 * x[0], 7.0
'''


def _kernel_dir(tmp_path):
    """The benchmark's kernel files, a grouped matmul with its own work
    module, and a backward kernel whose prefix extends the forward's."""
    shutil.copytree(catalog.HERE / "kernels", tmp_path / "kernels")
    (tmp_path / "kernels" / "moe_gmm_pallas.json").write_text(
        json.dumps(dict(metric="moe_gmm", work="moe_gmm_pallas.py")))
    (tmp_path / "kernels" / "moe_gmm_pallas.py").write_text(WORK)
    (tmp_path / "kernels" / "flash_attention_pallas_bwd.json").write_text(
        json.dumps(dict(metric="flash_attention_bwd", work="matmul")))


def test_kernel_file_with_its_own_work_is_joined(tmp_path):
    _kernel_dir(tmp_path)
    table = catalog.kernels(tmp_path)
    assert set(table) == {"dequant_matmul_flat_pallas", "flash_attention_pallas",
                          "flash_attention_pallas_bwd", "moe_gmm_pallas"}
    attn = ([("bf16", (2, 256, 64))] * 3, ("bf16", (2, 256, 64)))
    calls = {"moe_gmm_pallas.3": ([("bf16", (100, 8))], ("bf16", (100, 8))),
             "flash_attention_pallas.1": attn,
             "flash_attention_pallas_bwd.2": ([("bf16", (512, 64)),
                                               ("bf16", (64, 32))],
                                              ("bf16", (512, 32)))}
    rec = dict(window=[0, 1e9], host=[], devices={"0": dict(ops=[
        ["moe_gmm_pallas.3", "custom-call", 0, 1e6],
        ["flash_attention_pallas.1", "custom-call", 2e6, 3e6],
        ["flash_attention_pallas_bwd.2", "custom-call", 4e6, 5e6]])})
    peaks = dict(bf16_flops_per_s=1e6, hbm_bytes_per_s=1e3)
    k = xtrace.kernel_summary(rec, calls, table, peaks)
    assert k["moe_gmm"]["calls"] == 1 and k["moe_gmm"]["flops"] == 300.0
    assert k["moe_gmm"]["least_s"] == pytest.approx(max(300 / 1e6, 7 / 1e3))
    assert k["flash_attention_fwd"]["calls"] == 1
    assert k["flash_attention_fwd"]["flops"] == 4 * 2 * 64 * 256 * 257 / 2
    assert k["flash_attention_bwd"]["calls"] == 1
    assert k["flash_attention_bwd"]["flops"] == 2 * 512 * 64 * 32


@pytest.mark.parametrize("name, prefix", [
    ("flash_attention_pallas.18", "flash_attention_pallas"),
    ("flash_attention_pallas_bwd.2", "flash_attention_pallas_bwd"),
    ("flash_attention_pallas_bwd", "flash_attention_pallas_bwd"),
    ("quantize_int8_pallas.126", None),
], ids=["forward", "backward", "backward_unnumbered", "no_entry"])
def test_kernel_call_takes_the_longest_prefix(tmp_path, name, prefix):
    _kernel_dir(tmp_path)
    assert xtrace.kernel_of(name, catalog.kernels(tmp_path)) == prefix


def test_kernel_file_with_unknown_work_refused(tmp_path):
    _kernel_dir(tmp_path)
    (tmp_path / "kernels" / "other_pallas.json").write_text(
        json.dumps(dict(metric="other", work="grouped_matmul")))
    with pytest.raises(catalog.CellError, match="unknown work"):
        catalog.kernels(tmp_path)
