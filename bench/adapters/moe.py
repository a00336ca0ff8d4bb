"""Weights for a mixture-of-experts decoder (``moe``) configuration with
window and full attention layers, and the program's view of them.

The configuration file's top-level keys are the run's sizes, under the
names of the published ``config.json``; ``num_experts`` there counts the
experts this chip holds (from ``held_expert_first`` on), and the router
keeps the published count, ``hf_config["num_experts"]``.

As in ``dense.py``, whose prompt batch this family takes: the benchmark
makes the weights itself, from ``--seed``,
in one jitted call on the device and in the dtype they are served in; the
program gets the same arrays arranged as its own parameter pytree (no
copy); the reference in ``bench/reference/moe.py`` reads the benchmark's
dict, never the program's. Every matrix is N(0, initializer_range), norm
weights 1 + N(0, 0.1).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.adapters.dense import NORM_STD, is_norm, prefill_inputs  # noqa: F401

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def sizes(cfg: dict) -> dict:
    """The sizes the harness, the reference and the counts use."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rope = {}
    for layer_type, r in cfg["rope_parameters"].items():
        rope[KINDS[layer_type]] = {
            "theta": float(r["rope_theta"]),
            "yarn": r["rope_type"] == "yarn",
            "factor": float(r.get("factor", 1.0)),
            "orig": int(r.get("original_max_position_embeddings", 0)),
            "beta_fast": float(r.get("beta_fast", 32.0)),
            "beta_slow": float(r.get("beta_slow", 1.0)),
            "attention_factor": float(r.get("attention_factor", 1.0)),
        }
    return {
        "L": cfg["num_hidden_layers"], "d": d, "H": H,
        "K": cfg["num_key_value_heads"], "hd": cfg.get("head_dim") or d // H,
        "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "tied": bool(cfg.get("tie_word_embeddings", False)),
        "qk_norm": bool(cfg.get("qk_norm", False)),
        "init_std": cfg.get("initializer_range", 0.02),
        "kinds": tuple(KINDS[t] for t in cfg["layer_types"]),
        "window": cfg["sliding_window"], "rope": rope,
        "E": cfg["hf_config"]["num_experts"], "E_held": cfg["num_experts"],
        "E_first": cfg.get("held_expert_first", 0),
        "top_k": cfg["num_experts_per_tok"], "de": cfg["moe_intermediate_size"],
    }


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    s = sizes(cfg)
    L, d, H, K, hd, V = (s[k] for k in ("L", "d", "H", "K", "hd", "V"))
    Eh, de = s["E_held"], s["de"]
    out = {
        "embed": (V, d), "final_norm": (d,),
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, H, hd), "wk": (L, d, K, hd), "wv": (L, d, K, hd),
        "wo": (L, H, hd, d),
        "router": (L, d, s["E"]),
        "w_gate": (L, Eh, d, de), "w_up": (L, Eh, d, de), "w_down": (L, Eh, de, d),
    }
    if s["qk_norm"]:
        out["q_norm"] = (L, hd)
        out["k_norm"] = (L, hd)
    if not s["tied"]:
        out["unembed"] = (d, V)
    return out


def make_weights(cfg: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """All weights from ``key``, in one jitted call."""
    std = sizes(cfg)["init_std"]
    shp = shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shp.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            out[name] = (1.0 + NORM_STD * z if is_norm(name) else std * z).astype(dtype)
        return out

    return make(key)


def _period(kinds: tuple[str, ...]) -> tuple[str, ...]:
    """The shortest run of kinds that repeats to give every layer's."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds[:p] * (len(kinds) // p) == kinds:
            return kinds[:p]
    raise AssertionError("unreachable")


def program_config(cfg: dict, base):
    """The program's ArchConfig at exactly the file's sizes, holding the
    file's experts."""
    from repro.configs.base import RopeConfig

    s = sizes(cfg)
    r = s["rope"]
    rope_by_kind = tuple(
        (kind, RopeConfig(theta=k["theta"], yarn_factor=k["factor"],
                          original_max_positions=k["orig"], beta_fast=k["beta_fast"],
                          beta_slow=k["beta_slow"], attention_factor=k["attention_factor"]))
        for kind, k in r.items() if k["yarn"])
    return dataclasses.replace(
        base, n_layers=s["L"], d_model=s["d"], n_heads=s["H"], n_kv_heads=s["K"],
        head_dim=s["hd"], vocab=s["V"],
        rope_theta=next(k["theta"] for k in r.values() if not k["yarn"]),
        norm_eps=s["eps"], tie_embeddings=s["tied"], qk_norm=s["qk_norm"],
        sliding_window=s["window"], layer_types=_period(s["kinds"]),
        rope_by_kind=rope_by_kind, dtype=cfg["dtype"],
        moe=dataclasses.replace(base.moe, n_experts=s["E"], top_k=s["top_k"], n_shared=0,
                                d_expert=s["de"], n_held=s["E_held"],
                                first_held=s["E_first"]))


def to_program(W: dict) -> dict:
    """The program's parameter pytree over the same arrays."""
    from repro.models.attention import AttnParams

    params = {
        "embed": W["embed"],
        "final_norm": W["final_norm"],
        "layers": {
            "ln1": W["ln1"], "ln2": W["ln2"],
            "attn": AttnParams(wq=W["wq"], wk=W["wk"], wv=W["wv"], wo=W["wo"],
                               q_norm=W.get("q_norm"), k_norm=W.get("k_norm")),
            "mlp": {"router": W["router"], "w_gate": W["w_gate"], "w_up": W["w_up"],
                    "w_down": W["w_down"]},
        },
    }
    if "unembed" in W:
        params["unembed"] = W["unembed"]
    return params
