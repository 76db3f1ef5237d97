"""A configuration file (``configs/<name>.json``) and the program's
``ModelConfig`` built from it.

The file holds the published config's keys as the cell runs them, plus
``registry`` (the program's config whose family, activation and routing
the cell keeps), ``reduced``, ``assumed`` and ``deployment``.  Every size
the program reads comes from the file, so the file is the configuration
as run.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib


def load(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def dims(spec: dict) -> dict:
    """The sizes the reference and the counts read, by short name."""
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    moe = spec.get("num_local_experts", 0) > 0
    return {
        "L": spec["num_hidden_layers"], "d": d, "H": H,
        "Hkv": spec["num_key_value_heads"],
        "hd": spec.get("head_dim") or d // H,
        "ff": spec["intermediate_size"], "V": spec["vocab_size"],
        "E": spec.get("num_local_experts", 0) if moe else 0,
        "k": spec.get("num_experts_per_tok", 0) if moe else 0,
        "tied": bool(spec["tie_word_embeddings"]),
        "theta": float(spec["rope_theta"]),
        "eps": float(spec["rms_norm_eps"]),
    }


def model_config(spec: dict):
    """The program's ``ModelConfig`` at the file's sizes."""
    from repro.configs import get_config
    base = get_config(spec["registry"])
    m = dims(spec)
    moe = base.moe
    if (moe is not None) != (m["E"] > 0):
        raise ValueError(f"{spec['registry']}: the file and the registry "
                         "disagree on whether the model has experts")
    if moe is not None:
        moe = dataclasses.replace(moe, n_experts=m["E"], top_k=m["k"],
                                  d_ff_expert=m["ff"])
    return dataclasses.replace(
        base, n_layers=m["L"], d_model=m["d"], n_heads=m["H"],
        n_kv_heads=m["Hkv"], head_dim=m["hd"], d_ff=m["ff"], vocab=m["V"],
        tie_embeddings=m["tied"], rope_theta=m["theta"], moe=moe)
