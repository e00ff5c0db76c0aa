"""A cell at smoke widths that a CPU test run can hold: the harness's own
path end to end, with the configuration and mix given as dicts."""
from __future__ import annotations

import copy
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent

TINY = {
    "name": "tiny-qwen", "source": "smoke widths of the qwen_dense family",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "program": {"qkv_bias": True, "qk_norm": True},
    "reference": "qwen_dense",
    "weights_seed": 0,
    "serving": {"exit_layer": 2, "n_slots": 8, "max_len": 48,
                "page_size": 8, "n_pages": 64, "queue_depth": 4,
                "c_thr": 0.017},
    "limits": {"max_logit_gap": 1e-2, "band": 0.15},
}


def mix(kind: str = "offline") -> dict:
    with open(CHIP / "traffic" / f"{kind}.p25.json") as f:
        m = json.load(f)
    m.update(prompt={"median": 12, "sigma": 0.6, "buckets": [8, 16, 32]},
             output={"median": 6, "sigma": 0.6, "min": 3, "max": 16},
             n_requests=2048, block=16, preroll_s=0.5)
    if m["kind"] == "poisson":
        m.update(rate_per_s=40.0)
    return m


def spec(kind: str = "offline", mix_overrides=None, c_thr=None,
         conf=None) -> dict:
    conf = copy.deepcopy(TINY if conf is None else conf)
    if c_thr is not None:
        conf["serving"]["c_thr"] = c_thr
    m = mix(kind)
    m.update(mix_overrides or {})
    return {"cell": {"name": f"tiny.{kind}", "chips": 1}, "conf": conf,
            "mix": m,
            "end_to_end": [{"name": n, "unit": u} for n, u in (
                ("tokens_per_s", "tokens/s"), ("ttft_p95_ms", "ms"),
                ("itl_p50_ms", "ms"), ("itl_p95_ms", "ms"),
                ("setup_s", "s"))],
            "per_layer": []}
