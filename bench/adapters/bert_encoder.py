"""The BERT encoder's configuration and weights in the program's form.

The benchmark makes the weights itself (``references/bert_encoder``);
this file renames them into the program's parameter tree and back, so
that per-leaf numbers of the program and the reference can be compared
by the reference's names, and builds the ``ModelConfig`` from the sizes
in bench/configs/<config>.json.
"""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs import registry
    run = cfg["run"]
    return registry.get_config(run["registry"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        max_seq=cfg["max_position_embeddings"],
        norm_eps=cfg["layer_norm_eps"], activation=cfg["hidden_act"],
        rope_theta=float(cfg["rope_theta"]),
        **run.get("overrides", {}))


def to_program(w: dict) -> dict:
    L = w["layers"]
    block = {
        "norm1": {"g": L["ln1_g"], "b": L["ln1_b"]},
        "mixer": {"wq": {"w": L["wq"]}, "wk": {"w": L["wk"]},
                  "wv": {"w": L["wv"]}, "wo": {"w": L["wo"]}},
        "norm2": {"g": L["ln2_g"], "b": L["ln2_b"]},
        "ffn": {"up": {"w": L["up"]}, "down": {"w": L["down"]}},
    }
    return {"embed": w["embed"], "pos": w["pos"],
            "final_norm": {"g": w["final_g"], "b": w["final_b"]},
            "lm_head": {"w": w["head"]}, "periods": [block]}


def from_program(p: dict) -> dict:
    b = p["periods"][0]
    return {
        "embed": p["embed"], "pos": p["pos"],
        "final_g": p["final_norm"]["g"], "final_b": p["final_norm"]["b"],
        "head": p["lm_head"]["w"],
        "layers": {
            "ln1_g": b["norm1"]["g"], "ln1_b": b["norm1"]["b"],
            "ln2_g": b["norm2"]["g"], "ln2_b": b["norm2"]["b"],
            "wq": b["mixer"]["wq"]["w"], "wk": b["mixer"]["wk"]["w"],
            "wv": b["mixer"]["wv"]["w"], "wo": b["mixer"]["wo"]["w"],
            "up": b["ffn"]["up"]["w"], "down": b["ffn"]["down"]["w"],
        },
    }
