"""Configuration files, seeds, and the program's view of a configuration.

A configuration file (``bench/configs/<name>.json``) states the model's
sizes under the keys of its published ``config.json`` as the cell runs
them, plus what the program needs that the published config does not say
(``dtype``, ``param_dtype``, ``optstate_dtype``, ``remat``).  This module is
the one place that turns such a file into the program's ``ModelConfig``.
"""
from __future__ import annotations

import hashlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]      # the checkout
BENCH = ROOT / "bench"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    spec = benchmark()
    for w in spec["workloads"]:
        if w["name"] == name:
            cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
            return {"benchmark": spec, "workload": w, "config_entry": cfg,
                    "config": load_json(ROOT / cfg["file"]),
                    "mix": load_json(BENCH / "traffic" /
                                     f"{w['traffic']}.json"),
                    "run_seconds": spec["run_seconds"]}
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def derive_seed(seed: int, *parts) -> int:
    """A 31-bit seed for one use (``parts`` name it) of the run's seed:
    the same run seed always gives the same derived seeds."""
    h = hashlib.sha256(repr((int(seed),) + parts).encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


def program_config(c: dict, name: str):
    """Register ``c`` with the program under ``name`` (once) and return
    the program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig, get_config, register
    try:
        return get_config(name)
    except KeyError:
        pass
    if c["hidden_act"] != "silu" or not c["tie_word_embeddings"]:
        raise ValueError("only SwiGLU decoders with tied embeddings are "
                         "described by this adapter")
    return register(ModelConfig(
        name=name, family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        layer_pattern=("global",), mlp="swiglu", norm="rmsnorm",
        tie_embeddings=True, rope_theta=float(c["rope_theta"]),
        dtype=c["dtype"], param_dtype=c["param_dtype"],
        optstate_dtype=c.get("optstate_dtype", "float32"),
        remat=c.get("remat", "full")))
