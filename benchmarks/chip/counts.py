"""Operations and bytes the training step needs, computed from a
configuration file's sizes and a traffic file's update rule.

These are the counts of a dense decoder (`"counts": "dense"` in a
configuration file). A configuration whose count differs (experts, other
mixers) names its own module, `counts_<name>.py` beside this one, with the
same functions; `flops_per_token` and `update_bytes` hand such a
configuration to it.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _own(cfg: dict):
    """The count module a configuration names, or None for `dense`."""
    name = cfg.get("counts", "dense")
    if name == "dense":
        return None
    spec = importlib.util.spec_from_file_location(
        f"counts_{name}", os.path.join(HERE, f"counts_{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def params(cfg: dict) -> dict:
    """Parameter counts: `total`, `matmul` (every weight that a token's
    forward multiplies with: all layers and the output head, not the
    embedding lookup), `norm` (float32 scales) and `matrix` (the rest)."""
    d = int(cfg["hidden_size"])
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    f, V, L = int(cfg["intermediate_size"]), int(cfg["vocab_size"]), int(cfg["num_hidden_layers"])
    dh = d // H
    attn = d * H * dh + 2 * d * K * dh + H * dh * d
    ffn = 3 * d * f
    norm = L * 2 * d + d
    embed = V * d
    head = V * d
    tied = bool(cfg["tie_word_embeddings"])
    total = L * (attn + ffn) + norm + embed + (0 if tied else head)
    return {"total": total, "matmul": L * (attn + ffn) + head, "norm": norm,
            "matrix": total - norm}


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6 per matmul
    weight, plus 6 L d S for causal attention (QK^T and PV, half the square,
    three passes). Recomputation under remat does not count."""
    own = _own(cfg)
    if own is not None:
        return own.flops_per_token(cfg, seq_len)
    L, d = int(cfg["num_hidden_layers"]), int(cfg["hidden_size"])
    return 6.0 * params(cfg)["matmul"] + 6.0 * L * d * seq_len


#: bytes of one element: the weight dtype of matrices; norms are float32
_WEIGHT_BYTES = {"bfloat16": 2, "float32": 4}


def update_bytes(cfg: dict, traffic: dict) -> float:
    """HBM bytes one optimizer step needs over all leaves: read w and g,
    write w; read w_stale where DC-ASGD's term is on (lambda != 0); read and
    write Adam's float32 m and v. What the algorithm needs, not what a kernel
    happens to read."""
    own = _own(cfg)
    if own is not None:
        return own.update_bytes(cfg, traffic)
    p = params(cfg)
    dc = traffic["strategy"] == "dc_asgd" and float(traffic.get("dc_lambda", 0.04)) != 0.0
    adam = traffic["optimizer"] == "adam"

    def per_elem(wb):
        return 3 * wb + (wb if dc else 0) + (16 if adam else 0)

    return (p["matrix"] * per_elem(_WEIGHT_BYTES[cfg["torch_dtype"]])
            + p["norm"] * per_elem(4))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks from `peaks.json`; an unknown chip is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json "
                       f"(known: {', '.join(sorted(table))})")
    return table[device_kind]
