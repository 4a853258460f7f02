"""Process-global MoE routing-load accounting (docs/DESIGN.md §13).

The MoE dispatch records, from inside jitted prefill/decode steps, how many
(token, expert) assignments each step actually executed and how many were
dropped by the capacity limit (``mypos >= cap`` in the slot routing).  The
counters are process-global — accumulated via ``jax.debug.callback`` — so
each serving engine snapshots a baseline at construction and reports its own
delta in ``latency_stats()``.

Together with the static per-expert work table
(:meth:`repro.core.kneading.KneadedWeight.work_table`) this is the input the
ROADMAP work-stealing item needs: the table says how much kneaded work each
expert *owns*, the counters say how much traffic routing actually *sends*.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
import jax.numpy as jnp

_LOCK = threading.Lock()
_ROUTED = 0          # (token, expert) assignments executed (within capacity)
_DROPPED = 0         # assignments dropped by the capacity limit
_STEPS = 0           # routed MoE layer applications recorded


def _accumulate(routed, dropped) -> None:
    global _ROUTED, _DROPPED, _STEPS
    with _LOCK:
        _ROUTED += int(routed)
        _DROPPED += int(dropped)
        _STEPS += 1


def record_routing(eids: jax.Array, num_experts: int, cap: int) -> None:
    """Record one MoE layer's routing load.  Call from inside jit.

    ``eids`` [T, k] are the (replicated) global expert assignments; drops
    are derived from the per-expert histogram — expert e drops
    ``max(0, count_e - cap)`` assignments, exactly the ``mypos >= cap``
    overflow of the slot routing (position within an expert is global
    arrival order, so the histogram form is equivalent and O(T*k + E)
    instead of O(T*k*E)).
    """
    flat_e = eids.reshape(-1)
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat_e].add(1)
    dropped = jnp.sum(jnp.maximum(counts - cap, 0))
    routed = flat_e.shape[0] - dropped
    jax.debug.callback(_accumulate, routed, dropped)


def routing_stats() -> Dict[str, int]:
    """Cumulative routing-load counters (flushes pending callbacks)."""
    jax.effects_barrier()
    with _LOCK:
        return {"routed_tokens": _ROUTED,
                "capacity_dropped": _DROPPED,
                "routing_steps": _STEPS}


def reset_routing_stats() -> None:
    global _ROUTED, _DROPPED, _STEPS
    jax.effects_barrier()
    with _LOCK:
        _ROUTED = _DROPPED = _STEPS = 0
