"""Why a MoE cell has to serve through the sync path, and the check's
control, at tiny sizes on the CPU.

The decode plane executes each MoE layer with a plan routed one token
earlier, from the previous layer's residual; the published block routes
each token on its own post-attention state.  The check tells the two
apart.  And the control, the reference in fp8 put in the program's place,
goes through the check's verdict and comes out not correct where a sound
bf16 run is correct."""
from __future__ import annotations

from bench import run
from bench.tests import tiny

SEED = 2**31 + 9


def test_decode_plane_fails_the_check():
    hf = tiny.config(tiny.MOE, dtype="float32",
                     decode_plane=True, route_mode="lookahead")
    res = run.run_cell(tiny.cell(hf, tiny.MIX_CLOSED), SEED, 1.5, False, require_tpu=False)
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > tiny.LIMITS["numbers"]["max_logit_gap"]["limit"]


# the tiny dense cell's limit: its logits spread about 0.16, an eighth of
# the chip cells'; sound bf16 runs read 0 here, the fp8 control about 8e-3
TINY_DENSE_LIMIT = 0.004


def test_fp8_control_reads_above_the_limit():
    ex = {}
    hf = tiny.config("qwen3-32b-1chip")
    limits = {"sample": tiny.LIMITS["sample"],
              "numbers": {"max_logit_gap": {"limit": TINY_DENSE_LIMIT}}}
    res = run.run_cell(tiny.cell(hf, tiny.MIX_CLOSED, limits), SEED, 1.5, False,
                       require_tpu=False, control="fp8", extras=ex)
    sound = res["check"]["max_logit_gap"]["value"]
    assert res["correct"] and sound <= TINY_DENSE_LIMIT
    control = ex["verdict"]["control"]
    assert control["correct"] is False, control
    assert control["numbers"]["max_logit_gap"]["value"] > TINY_DENSE_LIMIT
