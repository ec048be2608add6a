"""How the benchmark hands a Qwen3-MoE configuration on one chip's share of
the experts, and its weights, to the program.

As `bench.program` does for the dense Qwen2: the program's `ModelConfig`
is built from the published sizes in ``bench/configs/<config>.json`` (the
registry entry only supplies defaults the file does not state), and its
parameter tree is re-nested from the weights that
`bench.reference.qwen3_moe.init_weights` made: the same arrays, no copies.
"""
from __future__ import annotations

from typing import Dict

from bench.program import import_program


def model_config(conf: Dict):
    """The program's `ModelConfig` for a published Qwen3-MoE configuration
    cut to one chip's share: the router keeps the published expert count
    and top-k, the chip holds ``num_experts`` of them from
    ``expert_offset``."""
    import_program()
    from repro.configs.base import get_config
    layers = int(conf["num_hidden_layers"])
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[
        conf["torch_dtype"]]
    return get_config(conf["program_config"]).scaled(
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]),
        d_ff=int(conf["intermediate_size"]), n_layers=layers,
        vocab_size=int(conf["vocab_size"]),
        layout=(((("attn", "moe"),), layers),),
        n_experts=int(conf["published"]["num_experts"]),
        top_k=int(conf["num_experts_per_tok"]),
        moe_dff=int(conf["moe_intermediate_size"]),
        experts_held=int(conf["num_experts"]),
        expert_offset=int(conf["expert_offset"]),
        qkv_bias=bool(conf["attention_bias"]), qk_norm=True,
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), activation="silu",
        window=None, attn_softcap=None, final_softcap=None,
        embed_scale=False, dtype=dtype)


def program_params(w: Dict):
    """The program's parameter tree (`repro.models.transformer`) over the
    benchmark's weights: one layout group of identical attention + MoE
    blocks, layers stacked on the leading axis."""
    block = {
        "norm1": {"scale": w["attn_norm"]},
        "attn": {"wq": {"w": w["wq"]}, "wk": {"w": w["wk"]},
                 "wv": {"w": w["wv"]}, "wo": {"w": w["wo"]},
                 "q_norm": {"scale": w["q_norm"]},
                 "k_norm": {"scale": w["k_norm"]}},
        "norm2": {"scale": w["mlp_norm"]},
        "moe": {"router": {"w": w["router"]}, "gate": w["w_gate"],
                "up": w["w_up"], "down": w["w_down"]},
    }
    return {"embed": {"table": w["embed"]},
            "lm_head": {"w": w["lm_head"]},
            "final_norm": {"scale": w["final_norm"]},
            "groups": [{"b0": block}]}


# program leaf path -> benchmark weight name (for per-leaf comparisons)
PROGRAM_LEAVES = {
    "embed/table": "embed", "lm_head/w": "lm_head",
    "final_norm/scale": "final_norm",
    "groups/0/b0/norm1/scale": "attn_norm",
    "groups/0/b0/attn/wq/w": "wq", "groups/0/b0/attn/wk/w": "wk",
    "groups/0/b0/attn/wv/w": "wv", "groups/0/b0/attn/wo/w": "wo",
    "groups/0/b0/attn/q_norm/scale": "q_norm",
    "groups/0/b0/attn/k_norm/scale": "k_norm",
    "groups/0/b0/norm2/scale": "mlp_norm",
    "groups/0/b0/moe/router/w": "router",
    "groups/0/b0/moe/gate": "w_gate", "groups/0/b0/moe/up": "w_up",
    "groups/0/b0/moe/down": "w_down",
}


def bench_leaves(tree) -> Dict:
    """A program parameter-shaped tree flattened to benchmark names."""
    def get(path):
        node = tree
        for p in path.split("/"):
            node = node[int(p)] if p.isdigit() else node[p]
        return node
    return {name: get(path) for path, name in PROGRAM_LEAVES.items()}
