"""The latent-attention MoE decoder's configuration and weights in the
program's form.

The benchmark makes the weights itself (``references/mla_moe_decoder``),
so the program and the reference start from the same numbers.  This file
renames them into the program's parameter tree (the dense layers as the
prefix, one scanned period of one MoE block stacked over the rest) and
builds its ``ModelConfig`` from bench/configs/<config>.json, with the
chip's share of the experts (``n_routed_experts`` held of
``assumed.router_experts``).

The published model rotates interleaved rope pairs; the program rotates
halves.  ``to_program`` permutes the rope columns of ``wq`` (in every
head) and of ``wkv_a`` from (x0, x1, x2, ...) to (x0, x2, ..., x1, x3,
...), so that the program's rotate-half applies the same rotation to the
same pairs: the q.k products, and so the model, are the same function.
"""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs import registry
    from repro.configs.base import MLACfg, MoECfg, YarnCfg
    run, rs, a = cfg["run"], cfg["rope_scaling"], cfg["assumed"]
    base = registry.get_config(run["registry"])
    if cfg["first_k_dense_replace"] != len(base.prefix):
        raise SystemExit("bench: the registry's prefix does not hold "
                         "first_k_dense_replace dense layers")
    return base.replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        activation=cfg["hidden_act"],
        rope_yarn=YarnCfg(
            factor=float(rs["factor"]),
            original_max_pos=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        mla=MLACfg(q_lora_rank=cfg["q_lora_rank"] or 0,
                   kv_lora_rank=cfg["kv_lora_rank"],
                   nope_dim=cfg["qk_nope_head_dim"],
                   rope_dim=cfg["qk_rope_head_dim"],
                   v_dim=cfg["v_head_dim"]),
        moe=MoECfg(n_experts=a["router_experts"],
                   top_k=cfg["num_experts_per_tok"],
                   d_ff=cfg["moe_intermediate_size"],
                   n_shared=cfg["n_shared_experts"],
                   norm_topk_prob=cfg["norm_topk_prob"],
                   routed_scale=float(cfg["routed_scaling_factor"]),
                   router_f32=True,
                   first_held=a["first_held_expert"],
                   n_held=cfg["n_routed_experts"]),
        **run.get("overrides", {}))


def _deinterleave(w, lo: int, hi: int):
    """Columns lo..hi of the last axis as (even, odd) pairs split."""
    import jax.numpy as jnp
    rope = w[..., lo:hi]
    return jnp.concatenate([w[..., :lo], rope[..., 0::2], rope[..., 1::2],
                            w[..., hi:]], axis=-1)


def _attention(L: dict) -> dict:
    n, d, hq = L["wq"].shape
    rank = L["kv_norm"].shape[-1]
    rope = L["wkv_a"].shape[-1] - rank
    # wq holds H (nope + rope) columns, wkv_b H (nope + v), wo H v rows
    heads = (hq - L["wkv_b"].shape[-1] + L["wo"].shape[1]) // rope
    qk = hq // heads
    wq = _deinterleave(L["wq"].reshape(n, d, heads, qk), qk - rope, qk)
    return {"wq": {"w": wq.reshape(n, d, hq)},
            "wkv_a": {"w": _deinterleave(L["wkv_a"], rank, rank + rope)},
            "kv_norm": {"g": L["kv_norm"]}, "wkv_b": {"w": L["wkv_b"]},
            "wo": {"w": L["wo"]}}


def to_program(w: dict) -> dict:
    D, M = w["dense"], w["moe"]
    prefix = [{"norm1": {"g": D["ln1"][i]},
               "mixer": {k: {n: a[i] for n, a in v.items()}
                         for k, v in _attention(D).items()},
               "norm2": {"g": D["ln2"][i]},
               "ffn": {"gate": {"w": D["wg"][i]}, "up": {"w": D["wu"][i]},
                       "down": {"w": D["wd"][i]}}}
              for i in range(D["ln1"].shape[0])]
    block = {
        "norm1": {"g": M["ln1"]},
        "mixer": _attention(M),
        "norm2": {"g": M["ln2"]},
        "ffn": {"router": M["router"], "gate": M["eg"], "up": M["eu"],
                "down": M["ed"],
                "shared": {"gate": {"w": M["sg"]}, "up": {"w": M["su"]},
                           "down": {"w": M["sd"]}}},
    }
    return {"embed": w["embed"], "final_norm": {"g": w["final_norm"]},
            "lm_head": {"w": w["head"]}, "prefix": prefix,
            "periods": [block]}
