"""Weights for a decoder-only (``dense``) configuration, and the program's
view of them.

The benchmark makes the weights itself, from ``--seed``, in one jitted call
on the device and in the dtype they are served in. The program gets the
same arrays arranged as its own parameter pytree (no copy); the reference in
``bench/reference/dense.py`` reads the benchmark's dict, never the program's.

Every matrix is N(0, initializer_range), as the published configs'
``initializer_range`` states; norm weights are 1 + N(0, 0.1), so that a
program that ignored them would be seen.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def sizes(cfg: dict) -> dict:
    """The sizes the harness, the reference and the counts use."""
    h = cfg["hf_config"]
    d, H = h["hidden_size"], h["num_attention_heads"]
    return {
        "L": h["num_hidden_layers"], "d": d, "H": H,
        "K": h["num_key_value_heads"], "hd": h.get("head_dim") or d // H,
        "ff": h["intermediate_size"], "V": h["vocab_size"],
        "eps": h["rms_norm_eps"], "theta": float(h["rope_theta"]),
        "tied": bool(h.get("tie_word_embeddings", False)),
        "qk_norm": bool(cfg.get("qk_norm", False)),
        "init_std": h.get("initializer_range", 0.02),
    }


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    s = sizes(cfg)
    L, d, H, K, hd, ff, V = (s[k] for k in ("L", "d", "H", "K", "hd", "ff", "V"))
    out = {
        "embed": (V, d), "final_norm": (d,),
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, H, hd), "wk": (L, d, K, hd), "wv": (L, d, K, hd),
        "wo": (L, H, hd, d),
        "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d),
    }
    if s["qk_norm"]:
        out["q_norm"] = (L, hd)
        out["k_norm"] = (L, hd)
    if not s["tied"]:
        out["unembed"] = (d, V)
    return out


def is_norm(name: str) -> bool:
    return "norm" in name or name in ("ln1", "ln2")


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


def program_config(cfg: dict, base):
    """The program's ArchConfig at exactly the file's sizes."""
    s = sizes(cfg)
    return dataclasses.replace(
        base, n_layers=s["L"], d_model=s["d"], n_heads=s["H"], n_kv_heads=s["K"],
        head_dim=s["hd"], d_ff=s["ff"], vocab=s["V"], rope_theta=s["theta"],
        norm_eps=s["eps"], tie_embeddings=s["tied"], qk_norm=s["qk_norm"],
        sliding_window=None, dtype=cfg["dtype"], moe=None)


def prefill_inputs(cfg: dict, S: int, make) -> dict:
    """The batch ``prefill_jit`` takes for a prompt of S tokens, built by
    ``make(shape, dtype)`` (shapes only, for compiling without a chip)."""
    return {"tokens": make((1, S), jnp.int32)}


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
            "mlp": {"w_gate": W["w_gate"], "w_up": W["w_up"], "w_down": W["w_down"]},
        },
    }
    if "unembed" in W:
        params["unembed"] = W["unembed"]
    return params
