"""Operations and bytes of the work, from shapes alone.

The roofline counts the work whatever implements it, so a change of kernel
changes none of this arithmetic:

* a matmul call of ``rows`` live rows against a logical [K, N] weight does
  2 * rows * K * N operations;
* it must at least read the activations at their dtype, the weights at one
  byte each (the least an 8-bit weight needs), one f32 scale per output
  channel, and write the output at the activation dtype;
* its least time is the larger of operations over the bf16 peak and bytes
  over the HBM bandwidth.

Padding, bit-plane passes and multi-pass f32 contractions therefore all
show up as lost share.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

Call = Tuple[int, int, int]          # (live rows, logical K, logical N)

def matmul_flops(rows: int, k: int, n: int) -> float:
    return 2.0 * rows * k * n


def matmul_bytes(rows: int, k: int, n: int, act_bytes: int) -> float:
    return float(rows * k * act_bytes + k * n + 4 * n + rows * n * act_bytes)


def least_time_s(calls: Iterable[Call], act_bytes: int,
                 peaks: Dict[str, float]) -> Tuple[float, float, float]:
    """(least seconds, flop-bound seconds, byte-bound seconds) summed over
    calls; each call is bound by the larger of its two times."""
    least = flop_s = byte_s = 0.0
    for rows, k, n in calls:
        f = matmul_flops(rows, k, n) / peaks["bf16_flops_per_s"]
        b = matmul_bytes(rows, k, n, act_bytes) / peaks["hbm_bytes_per_s"]
        least += max(f, b)
        flop_s += f
        byte_s += b
    return least, flop_s, byte_s


# ------------------------------------------------------------ LM

def lm_projections(model: Dict) -> List[Tuple[int, int]]:
    """Logical [K, N] of every projection of one dense decoder layer that
    the kneaded path serves: q, k, v, o, gate, up, down."""
    d = model["d_model"]
    hd = model.get("head_dim") or d // model["num_heads"]
    q = model["num_heads"] * hd
    kv = model["num_kv_heads"] * hd
    f = model["d_ff"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def lm_kneaded_calls(model: Dict, rows: int) -> List[Call]:
    """The kneaded-kernel calls of one forward over ``rows`` live rows."""
    return [(rows, k, n) for k, n in lm_projections(model)] * \
        model["num_layers"]


def lm_matmul_params(model: Dict) -> int:
    """Weights every token multiplies: the layers' projections and the
    (tied or separate) output head."""
    per_layer = sum(k * n for k, n in lm_projections(model))
    return model["num_layers"] * per_layer + model["d_model"] * \
        model["vocab_size"]


def lm_attention_flops(model: Dict, context: int) -> float:
    """Score and value products of one query row over ``context`` keys,
    all layers."""
    hd = model.get("head_dim") or model["d_model"] // model["num_heads"]
    return 4.0 * model["num_heads"] * hd * context * model["num_layers"]


def lm_decode_flops(model: Dict, contexts: Iterable[int]) -> float:
    """Model operations of one decode step: one row per live request,
    attending over its own context."""
    total = 0.0
    for ctx in contexts:
        total += 2.0 * lm_matmul_params(model) + lm_attention_flops(model,
                                                                    ctx)
    return total


# ------------------------------------------------------------ CNN

def cnn_layers(model: Dict) -> List[Dict]:
    """Every conv and fc layer of a plain CNN spec with its output size:
    ``{"kind", "rows" (per image), "k", "n"}`` (conv: im2col K = C*k*k)."""
    c = model["in_channels"]
    size = model["image_size"]
    flat = None
    out = []
    for item in model["spec"]:
        kind = item[0]
        if kind == "conv":
            _, out_c, k, stride = item
            size //= stride
            out.append({"kind": "conv", "rows": size * size, "k": c * k * k,
                        "n": out_c, "ksize": k, "in_c": c})
            c = out_c
        elif kind == "pool":
            size //= item[1]
        elif kind == "fc":
            d_in = flat if flat is not None else c * size * size
            out.append({"kind": "fc", "rows": 1, "k": d_in, "n": item[1]})
            flat = item[1]
    return out


def cnn_macs(model: Dict) -> float:
    return float(sum(l["rows"] * l["k"] * l["n"] for l in cnn_layers(model)))


def cnn_kneaded_calls(model: Dict, images: int) -> List[Call]:
    return [(l["rows"] * images, l["k"], l["n"]) for l in cnn_layers(model)]
