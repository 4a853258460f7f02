"""Small sizes of the benchmark's two configurations and mixes, for
driving a whole run on the CPU (the Pallas kernel interprets there)."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

LM_MODEL = {"num_layers": 2, "d_model": 128, "num_heads": 2,
            "num_kv_heads": 1, "d_ff": 256, "vocab_size": 512}
LM_MIX = {"arrival": {"kind": "poisson", "rate_per_s": 6.0},
          "prompt_tokens": {"values": [16, 32], "weights": [0.5, 0.5]},
          "output_tokens": {"values": [4, 8], "weights": [0.5, 0.5]},
          "ramp_s": 0.5, "tail_s": 30, "trace_s": 1,
          "serving": {"max_inflight": 4, "buckets": [1, 2], "kv_block": 64,
                      "prefill_chunk": 64}}
CNN_MODEL = {"image_size": 16, "num_classes": 10,
             "spec": [["conv", 16, 3, 1], ["pool", 2], ["conv", 32, 3, 1],
                      ["pool", 2], ["fc", 64], ["fc", 10]]}
CNN_MIX = {"images": 4, "ramp_s": 0.2, "trace_s": 0.5}


def lm(cfg, mix):
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(LM_MODEL)
    cfg["check"]["requests"] = 3
    return cfg, {**mix, **copy.deepcopy(LM_MIX)}


def cnn(cfg, mix):
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(copy.deepcopy(CNN_MODEL))
    cfg["check"]["images"] = 3
    return cfg, {**mix, **CNN_MIX}
