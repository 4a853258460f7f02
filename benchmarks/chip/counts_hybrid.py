"""The kneaded calls of a hybrid decoder with a layer period (granite-4.0-h:
``attn_period`` layers, one of them attention, the rest Mamba-2, each
followed by an MLP), from shapes alone; their least time is
``counts.least_time_s``.

Per layer and live row the kernel runs, at logical [K, N]:

* a Mamba-2 layer: ``in_proj`` [d, 2 d_inner + 2 d_state + heads] and
  ``out_proj`` [d_inner, d];
* an attention layer: q [d, heads x hd], k and v [d, kv heads x hd] and
  o [heads x hd, d];
* every layer: gate and up [d, d_ff] and down [d_ff, d].

The tied head is a float matmul, not a kneaded call.  A decode step's
model operations (``hybrid_decode_flops``, for the step's share of the
chip's peak) are 2 x every kneaded K x N and the tied head per live row,
attention over each row's context on the attention layers, and each Mamba-2
layer's state update and read: 6 x d_inner x d_state a row (the decay, the
outer product dt x (x) B and their sum; C . h).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from .counts import Call


def _mlp(model: Dict) -> List[Tuple[int, int]]:
    d, f = model["d_model"], model["d_ff"]
    return [(d, f), (d, f), (f, d)]


def mamba_projections(model: Dict) -> List[Tuple[int, int]]:
    d = model["d_model"]
    di = model["ssm_expand"] * d
    heads = di // model["ssm_head_dim"]
    return [(d, 2 * di + 2 * model["ssm_state"] + heads), (di, d)] + \
        _mlp(model)


def attention_projections(model: Dict) -> List[Tuple[int, int]]:
    d = model["d_model"]
    hd = model.get("head_dim") or d // model["num_heads"]
    q, kv = model["num_heads"] * hd, model["num_kv_heads"] * hd
    return [(d, q), (d, kv), (d, kv), (q, d)] + _mlp(model)


def hybrid_kneaded_calls(model: Dict, rows: int) -> List[Call]:
    """The kneaded-kernel calls of one forward over ``rows`` live rows."""
    n_attn = model["num_layers"] // model["attn_period"]
    n_mamba = model["num_layers"] - n_attn
    return ([(rows, k, n) for k, n in mamba_projections(model)] * n_mamba
            + [(rows, k, n) for k, n in attention_projections(model)]
            * n_attn)


def hybrid_decode_flops(model: Dict, contexts: Iterable[int]) -> float:
    """Model operations of one decode step: one row per live request,
    attending over its own context on the attention layers."""
    n_attn = model["num_layers"] // model["attn_period"]
    n_mamba = model["num_layers"] - n_attn
    hd = model.get("head_dim") or model["d_model"] // model["num_heads"]
    weights = sum(k * n for _, k, n in hybrid_kneaded_calls(model, 1)) + \
        model["d_model"] * model["vocab_size"]
    state = 6.0 * model["ssm_expand"] * model["d_model"] * \
        model["ssm_state"] * n_mamba
    return sum(2.0 * weights + state
               + 4.0 * model["num_heads"] * hd * ctx * n_attn
               for ctx in contexts)
