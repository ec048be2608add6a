"""How the benchmark hands its configuration and weights to the program.

The program under test is the `repro` package under ``<checkout>/src``.  Its
model configuration is built here from the published sizes in
``bench/configs/<config>.json`` (the registry entry only supplies defaults
that the published config does not state), and its parameter tree is
re-nested from the weights that `bench.reference.qwen2.init_weights` made:
the same arrays, no copies.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"


def import_program():
    """Put ``<checkout>/src`` on the path; fail when the checkout has none."""
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"bench: no program under {SRC}: run from a "
                         "checkout of the repository")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def model_config(conf: Dict):
    """The program's `ModelConfig` for a published dense Qwen2-style
    configuration, every size taken from ``conf``."""
    import_program()
    from repro.configs.base import get_config
    heads = int(conf["num_attention_heads"])
    layers = int(conf["num_hidden_layers"])
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[
        conf["torch_dtype"]]
    return get_config(conf["program_config"]).scaled(
        d_model=int(conf["hidden_size"]), n_heads=heads,
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["hidden_size"]) // heads,
        d_ff=int(conf["intermediate_size"]), n_layers=layers,
        vocab_size=int(conf["vocab_size"]),
        layout=(((("attn", "dense"),), layers),),
        qkv_bias=True, tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), activation="silu",
        window=None, attn_softcap=None, final_softcap=None,
        embed_scale=False, dtype=dtype)


def program_params(w: Dict):
    """The program's parameter tree (`repro.models.transformer`) over the
    benchmark's weights: one layout group of identical attention + dense
    blocks, layers stacked on the leading axis."""
    block = {
        "norm1": {"scale": w["attn_norm"]},
        "attn": {"wq": {"w": w["wq"], "b": w["bq"]},
                 "wk": {"w": w["wk"], "b": w["bk"]},
                 "wv": {"w": w["wv"], "b": w["bv"]},
                 "wo": {"w": w["wo"]}},
        "norm2": {"scale": w["mlp_norm"]},
        "mlp": {"gate": {"w": w["w_gate"]}, "up": {"w": w["w_up"]},
                "down": {"w": w["w_down"]}},
    }
    return {"embed": {"table": w["embed"]},
            "final_norm": {"scale": w["final_norm"]},
            "groups": [{"b0": block}]}


# program leaf path -> benchmark weight name (for per-leaf comparisons)
PROGRAM_LEAVES = {
    "embed/table": "embed", "final_norm/scale": "final_norm",
    "groups/0/b0/norm1/scale": "attn_norm",
    "groups/0/b0/attn/wq/w": "wq", "groups/0/b0/attn/wq/b": "bq",
    "groups/0/b0/attn/wk/w": "wk", "groups/0/b0/attn/wk/b": "bk",
    "groups/0/b0/attn/wv/w": "wv", "groups/0/b0/attn/wv/b": "bv",
    "groups/0/b0/attn/wo/w": "wo",
    "groups/0/b0/norm2/scale": "mlp_norm",
    "groups/0/b0/mlp/gate/w": "w_gate", "groups/0/b0/mlp/up/w": "w_up",
    "groups/0/b0/mlp/down/w": "w_down",
}


def bench_leaves(tree) -> Dict:
    """A program parameter-shaped tree flattened to benchmark names."""
    def get(path):
        node = tree
        for p in path.split("/"):
            node = node[int(p)] if p.isdigit() else node[p]
        return node
    return {name: get(path) for path, name in PROGRAM_LEAVES.items()}
