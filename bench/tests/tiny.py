"""A tiny cell for CPU tests: the chip cell's configuration file, or a
Qwen3-MoE file of the same form, with every size cut down, the program path
the cells serve (for MoE: sync, dropless routing) and a short mix.  Not a
configuration of the benchmark."""
from __future__ import annotations

import copy
import json
from pathlib import Path
from typing import Optional

from bench import run

ROOT = Path(__file__).resolve().parents[2]

MOE_SIZES = {
    "hidden_size": 64, "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 256,
}
DENSE = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
}
FIELDS = dict(run.FIELDS, intermediate_size="d_ff")
# Qwen3-MoE in the form of a file under bench/configs, on the program's
# published preset: the published keys the sizes above leave, and the program
# path that computes the published block (each token routed on its own
# post-attention state, none dropped).  The name keeps the test ids stable.
MOE = "qwen3-moe-235b-a22b-1chip"
MOE_FILE = {
    "model_type": "qwen3_moe", "norm_topk_prob": True, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "tie_word_embeddings": False, "hidden_act": "silu",
    "reduced_from": {},
    "program": {"preset": "qwen3-moe-235b-a22b",
                "overrides": {"decode_plane": False, "route_mode": "sync",
                              "rope_theta": 1000000.0, "spec_tokens": 1}},
}


def config(name: str, *, dtype: str = "bfloat16", slots: int = 4,
           held: Optional[int] = None, share: int = 0, **program) -> dict:
    """A configuration cut to tiny sizes: ``MOE`` or the name of a file
    under bench/configs; ``program`` adds overrides.  For ``MOE`` the
    program holds every expert, or with ``held`` the share ``share`` of
    ``held`` experts behind the router's tiny width."""
    if name == MOE:
        hf = copy.deepcopy(MOE_FILE)
    else:
        hf = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    small = MOE_SIZES if name == MOE else DENSE
    hf.update(small)
    over = hf["program"]["overrides"]
    over.update({FIELDS[k]: v for k, v in small.items()})
    if "num_experts" in hf:
        router = small["num_experts"]
        over["capacity_factor"] = router / small["num_experts_per_tok"]
        over["d_ff"] = small["moe_intermediate_size"]
        if held is not None:
            hf.update(num_experts=held, expert_share=share,
                      reduced_from=dict(hf["reduced_from"], num_experts=router))
        over.update(experts_held=hf["num_experts"], expert_share=hf.get("expert_share", 0))
    over.update(dtype=dtype, param_dtype=dtype, use_pallas=False)
    over.update(program)
    hf["program"]["slots"] = slots
    return hf


MIX_CLOSED = {
    "arrival": {"process": "closed", "concurrency": "slots"},
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "buckets": [8, 16]},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 3, "max": 10},
    "first_cohort": "staggered", "requests": 64,
}
MIX_OPEN = {
    "arrival": {"process": "poisson", "rate_per_s": 8.0, "ramp_s": 0.5},
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "buckets": [8, 16]},
    "output": {"dist": "lognormal", "median": 5, "sigma": 0.5, "min": 2, "max": 8},
}
LIMITS = {"sample": {"max_requests": 3, "min_tokens": 16},
          "numbers": {"max_logit_gap": {"limit": 0.05}}}


def cell(hf: dict, mix: dict, limits: dict = LIMITS) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = spec["workloads"][0]["name"]
    c = dict(spec["workloads"][0], chips=1)
    return {"spec": spec, "cell": c, "hf": hf, "mix": copy.deepcopy(mix),
            "limits": copy.deepcopy(limits), "name": name}
