"""The dense decoder's configuration and weights in the program's form.

The benchmark makes the weights itself (``references/dense_decoder``),
so the program and the reference start from the same numbers and the
reference takes nothing the program made.  This file only renames them
into the program's parameter tree (one scanned period of one block,
stacked over layers) and builds its ``ModelConfig`` from the sizes in
bench/configs/<config>.json.
"""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs import registry
    run = cfg["run"]
    return registry.get_config(run["registry"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], qkv_bias=True,
        activation=cfg["hidden_act"], **run.get("overrides", {}))


def to_program(w: dict) -> dict:
    L = w["layers"]
    block = {
        "norm1": {"g": L["ln1"]},
        "mixer": {"wq": {"w": L["wq"], "b": L["bq"]},
                  "wk": {"w": L["wk"], "b": L["bk"]},
                  "wv": {"w": L["wv"], "b": L["bv"]},
                  "wo": {"w": L["wo"]}},
        "norm2": {"g": L["ln2"]},
        "ffn": {"gate": {"w": L["wg"]}, "up": {"w": L["wu"]},
                "down": {"w": L["wd"]}},
    }
    return {"embed": w["embed"], "final_norm": {"g": w["final_norm"]},
            "periods": [block]}
