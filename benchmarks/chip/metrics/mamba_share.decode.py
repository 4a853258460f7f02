"""Share of the decode steps' device time in the Mamba-2 mixers' own work
between their two projections (the conv, the state update and the gated
norm): the self time of those ops inside ``jit_decode_step`` runs over the
device time of the runs.

Match rule.  The program runs that work under the name scope
``mamba_mixer``, but a TPU trace's op events carry the HLO text without
its metadata, and XLA names fusions by number, so the scope never reaches
the trace.  The ops are matched by shape instead: an op that is neither
the SAC kernel nor a container (``while``, ``conditional``, ``call``) and
whose HLO text (output and operands) holds an f32 or bf16 array (an
activation, not the u32 bit planes of a weight) whose last dim is a
width only the mixer has (the inner width, the conv channels, in_proj's
output, padded or not) or whose last dims are the recurrent state's
[heads, head dim, state].  At granite's widths that is 4096, 4352, 8512,
8576 or [..., 64, 64, 128]; no attention, MLP or head array has one.
"""
import re

from benchmarks.chip import trace

_ARRAY = re.compile(r"\b(?:f32|bf16)\[([0-9,]+)\]")
_CONTAINERS = (" while(", " conditional(", " call(")


def mixer_dims(model):
    """(last dims, trailing state dims) that mark the mixer's arrays."""
    di = model["ssm_expand"] * model["d_model"]
    n, p = model["ssm_state"], model["ssm_head_dim"]
    proj = 2 * di + 2 * n + di // p
    return ({di, di + 2 * n, proj, -(-proj // 128) * 128},
            (di // p, p, n))


def is_mixer_op(op, model) -> bool:
    if trace.is_sac(op) or any(c in op.name for c in _CONTAINERS):
        return False
    last, state = mixer_dims(model)
    for m in _ARRAY.finditer(op.name):
        dims = tuple(int(d) for d in m.group(1).split(","))
        if dims[-1] in last or dims[-3:] == state:
            return True
    return False


def read(record, **_):
    s = record.get("summary")
    mods = s.modules("decode_step") if s is not None else []
    if not mods or "ssm_state" not in record["model"]:
        return None
    mixer = s.op_seconds(lambda o: is_mixer_op(o, record["model"]),
                         within="decode_step")
    step = sum(m.end - m.start for m in mods) * 1e-9
    return 100.0 * mixer / step if mixer > 0 else None
