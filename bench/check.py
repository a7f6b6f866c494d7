"""The output check that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished is drawn from the seed, the longest of them
always in it, until it holds ``min_tokens`` served tokens or
``max_requests`` requests.  The plain reference (``bench/reference``) runs
once over each sampled prompt followed by its served tokens, and reads, at
every served token, how far that token's logit lies below the reference's
best logit there.  Decoding is greedy, so a sound program serves the
reference's best token or one within rounding of it.

Numbers compared, each with its limit from ``bench/limits/<cell>.json``
(a cell's file names which of the two gaps it compares):

* ``max_logit_gap``: the widest such gap over the sample;
* ``mean_logit_gap``: their mean over every served token of the sample.
* ``wrong_lengths``: finished requests that served another number of tokens
  than they asked for (limit 0).
* ``finished``: requests the run finished (at least 1).

The control (``bench/calibrate.py``, never the benchmark's own runs) puts
the reference in fp8 in the program's place: at each served position it
serves the token that fp8 puts first, and goes through the same verdict,
which has to come out not correct.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def sample(finished: Dict[int, List[int]], seed: int, max_requests: int,
           min_tokens: int) -> List[int]:
    rids = sorted(finished)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(finished[r]), -r))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0xC4EC])
    chosen, total = [longest], len(finished[longest])
    for r in rng.permutation([r for r in rids if r != longest]):
        if len(chosen) >= max_requests or total >= min_tokens:
            break
        chosen.append(int(r))
        total += len(finished[int(r)])
    return chosen


def served_gaps(hf: Dict, seed: int, prompts: Sequence[np.ndarray],
                served: Sequence[Sequence[int]], *, control: str = ""):
    """Per request, at each served token, the reference's best logit minus
    the logit of the token served there.  With ``control`` (``"fp8"``) also
    the same for the token the reference in that precision puts first at
    the same positions (else None)."""
    from bench.reference import qwen3

    seqs, pos = [], []
    for p, s in zip(prompts, served):
        seqs.append(np.concatenate([np.asarray(p, np.int32), np.asarray(s[:-1], np.int32)]))
        pos.append(np.arange(len(p) - 1, len(p) - 1 + len(s)))
    ref = qwen3.logits_at(hf, seed, seqs, pos)

    def gap(picks):
        return [l.max(-1) - l[np.arange(len(t)), t] for l, t in zip(ref, picks)]

    sound = gap([np.asarray(s, np.int64) for s in served])
    if not control:
        return sound, None
    low = qwen3.logits_at(hf, seed, seqs, pos, quant=control)
    return sound, gap([np.argmax(l, -1) for l in low])


def gap_stats(gaps) -> Dict:
    flat = np.concatenate(gaps)
    return {"max_logit_gap": float(flat.max()), "mean_logit_gap": float(flat.mean()),
            "tokens": int(flat.size), "off_best": int((flat > 0).sum())}


def held(stats: Dict, limits: Dict):
    """Each number ``limits`` names beside its limit, and whether all hold."""
    numbers = {name: {"value": stats[name], "limit": spec["limit"]}
               for name, spec in limits["numbers"].items()}
    return numbers, all(v["value"] <= v["limit"] for v in numbers.values())


def check(hf: Dict, seed: int, finished: Dict[int, List[int]],
          prompts: Dict[int, np.ndarray], limits: Dict,
          outputs: Dict[int, int], *, control: str = "") -> Dict:
    """``correct``, each number compared beside its limit, the sample, and
    the gaps' statistics.  With ``control``, also ``control``: the same
    verdict for the reference in that precision put in the program's place
    (it served, at each position, the token it puts first)."""
    wrong = sum(1 for r, toks in finished.items() if len(toks) != outputs[r])
    numbers = {
        "finished": {"value": len(finished), "limit": ">= 1"},
        "wrong_lengths": {"value": wrong, "limit": 0},
    }
    ok = len(finished) >= 1 and wrong == 0
    smp = limits["sample"]
    chosen = sample(finished, seed, smp["max_requests"], smp["min_tokens"])
    out: Dict = {"correct": False, "numbers": numbers, "sampled": chosen, "stats": {}}
    if chosen:
        g, low = served_gaps(hf, seed, [prompts[r] for r in chosen],
                             [finished[r] for r in chosen], control=control)
        out["stats"] = gap_stats(g)
        compared, fine = held(out["stats"], limits)
        numbers.update(compared)
        ok = ok and fine
        if low is not None:
            cstats = gap_stats(low)
            cnumbers, cfine = held(cstats, limits)
            out["control"] = {"correct": cfine, "numbers": cnumbers, "stats": cstats}
    out["correct"] = bool(ok)
    return out
