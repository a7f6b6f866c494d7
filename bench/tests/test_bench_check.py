"""The output check on whole runs of the harness at tiny sizes on the CPU
(the chip check skipped): a sound run is correct, and each fault the cells
can have, planted under the timed path, makes ``correct`` false.

Faults: a decode step that returns its cache unchanged, and a token altered
where it is produced.  The cells run on one chip, so no exchange between
chips can be left out; they serve and do not train, so no batch mean can
lose half its rows.  The limit here is the tiny cell's (``tiny.LIMITS``):
logits of a 64-wide model are smaller than those of the chip cells."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import tiny

SEED = 2**31 + 3


def _run(hf, on_replica=None, mix=tiny.MIX_CLOSED, **kw):
    return run.run_cell(tiny.cell(hf, mix), SEED, 1.5, False, require_tpu=False,
                        on_replica=on_replica, **kw)


@pytest.mark.parametrize("name", [tiny.MOE, "qwen3-32b-1chip"])
def test_sound_run_is_correct(name):
    # float32: at 64 wide, bf16 rounding flips top-2-of-8 routing often
    # enough to move a logit by the tiny limit
    res = _run(tiny.config(name, dtype="float32"))
    assert res["correct"], res["check"]
    assert res["check"]["finished"]["value"] >= 1
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "check"


def test_open_loop_run_is_correct():
    res = _run(tiny.config("qwen3-32b-1chip", dtype="float32"), mix=tiny.MIX_OPEN)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_step_that_keeps_its_state_is_caught():
    def plant(rep):
        decode = rep._decode

        def unchanged(params, cache, *args):
            kept = jax.tree.map(jnp.copy, cache)  # the call donates its cache
            logits, _ = decode(params, cache, *args)
            return logits, kept

        rep._decode = unchanged

    res = _run(tiny.config("qwen3-32b-1chip"), plant)
    assert not res["correct"], res["check"]


def test_token_altered_where_produced_is_caught():
    def plant(rep):
        decode = rep._decode

        def shifted(params, cache, *args):
            logits, cache = decode(params, cache, *args)
            return jnp.roll(logits, 1, axis=-1), cache

        rep._decode = shifted

    res = _run(tiny.config(tiny.MOE), plant)
    assert not res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] > tiny.LIMITS["numbers"]["max_logit_gap"]["limit"]
