"""A small cell of the real configurations' code paths, for the CPU."""

import copy
import json
import pathlib

import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]

SMALL = {
    "mamba2-370m": (
        {"d_model": 64, "n_layer": 2, "vocab_size": 256, "d_state": 16,
         "headdim": 16, "chunk_size": 8, "dtype": "float32"},
        {"n_layers": 2, "d_model": 64, "vocab_size": 256, "ssm_state": 16,
         "ssm_head_dim": 16, "ssm_chunk": 8, "dtype": "float32", "loss_chunk": 16}),
    "qwen1.5-4b": (
        {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
         "intermediate_size": 128, "num_hidden_layers": 2, "vocab_size": 256,
         "torch_dtype": "float32"},
        {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 128,
         "vocab_size": 256, "dtype": "float32", "attn_chunk": 16, "loss_chunk": 16}),
}


def small_cell(config="mamba2-370m", *, workers=1, batch=2, seq=32, limits=None):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    sizes, replace = SMALL[config]
    cfg.update(sizes)
    cfg["program"] = {"arch": cfg["program"]["arch"],
                      "replace": {**cfg["program"]["replace"], **replace}}
    traffic = {"data": workers, "batch_per_worker": batch, "seq_len": seq,
               "tokens": {"zipf_exponent": 1.1},
               "method": {"compressor": "sparsign", "server": "majority_vote",
                          "budget_kind": "fixed", "budget": 1.0, "lr": 0.003,
                          "warmup": 10},
               "flags": ["--vote-impl", "allgather_packed", "--backend", "jnp"],
               "setup_steps": 3, "trace_steps": 2}
    return harness.Cell(
        name=f"{config}.small", chips=workers, config_name=config, config=cfg,
        traffic=traffic, limits=copy.deepcopy(limits or {}),
        reference=harness.load_module(BENCH / "configs" / f"{config}.py"),
        flops=harness.load_module(BENCH / "flops" / f"{config}.py"))
