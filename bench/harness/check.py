"""Is what the timed path served correct?

The sample is fixed by the seed before the window's requests finish: of
the requests due in the window, the one that asks for the most tokens
(prompt and output) and ``sample_requests - 1`` more drawn from the seed
(``sample``).  When the window closes the run keeps serving, offering
nothing new, until every sampled request has finished: a minute of
serving at most, counted from when serving resumes (a traced run stops its
profiler first and reads the trace only after; ``serve.Driver.drain``).  A
sampled request that has not finished by then counts as never answered.
Once the server's device state is freed, each is run once through the
plain reference (``hidden`` and ``head`` of the configuration's
architecture file, ``bench/arch/``) over its prompt and the tokens the
server produced.  Serving is greedy, so each served
token should be the reference's best or within rounding of it.  At each
served token the gap is how far its reference logit lies below the
reference's best logit there.  Read over every served token of the
sample:

* ``mean_gap``, the mean gap;
* ``not_best_share``, the share of served tokens that are not the
  reference's best;
* ``widest_gap``, the largest gap: set by rare near-ties (an expert or
  token chosen the other way) that bfloat16 and the controls all meet, so
  it does not tell them apart (PERF.md gives the readings).

Each number with a limit in the cell's file (``limits``) is compared with
it; PERF.md gives the readings each limit was set from.

A control (``control="fp8"`` or ``"int8"``, ``bench/control.py``) is the
reference computed in that type, put in the program's place: at the same
positions of the same sequences, the gap of the token it puts first.  A
control run also reports the served tokens' numbers, ``served_*``, beside
the control's, with no limit.

Three counts are compared with the limit 0: finished requests whose token
count differs from what they asked for, tokens outside the vocabulary, and
sampled requests that never finished.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from harness import weights


def sample(records, n: int, seed: int) -> List:
    """The requests to check, fixed before any has finished."""
    due = sorted((r for r in records if r.phase == "window" and not r.failed),
                 key=lambda r: r.req.rid)
    if not due:
        return []
    longest = max(due, key=lambda r: (r.req.prompt_len + r.req.output_len,
                                      -r.req.rid))
    rest = [r for r in due if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    pick = rng.permutation(len(rest))[:n - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def rows(chosen, generated, width: int):
    """tokens [n, width]: prompt then served tokens; targets [n, width]:
    at position p the served token the model was to put at p + 1."""
    n = len(chosen)
    toks = np.zeros((n, width), np.int32)
    tgt = np.full((n, width), -1, np.int32)
    for i, r in enumerate(chosen):
        out = np.asarray(generated[r.req.rid], np.int32)
        seq = np.concatenate([r.req.prompt, out])[:width]
        toks[i, :len(seq)] = seq
        P = r.req.prompt_len
        tgt[i, P - 1:P - 1 + len(out)] = out
    return toks, tgt


def run(cell, seed: int, records, generated: Dict[int, List[int]],
        chosen: List, control: Optional[str] = None) -> Dict:
    """``chosen``: the sample (``sample``).  ``control`` ("fp8" or
    "int8"): in place of the served tokens, read the tokens the reference
    computed in that type puts first at the same positions of the same
    sequences (a control, which a sound limit reads as not correct)."""
    conf, cp, mix, ref = cell.config, cell.params, cell.traffic, cell.arch
    V = conf["vocab_size"]
    limits = cp["limits"]
    finished = [r for r in records if r.finish is not None]
    miscount = sum(1 for r in finished
                   if len(generated.get(r.req.rid, ())) != r.req.output_len)
    outside = sum(1 for r in finished for t in generated.get(r.req.rid, ())
                  if not 0 <= t < V)
    unfinished = sum(1 for r in chosen if r.finish is None)
    checks = {"count_errors": [miscount, 0],
              "tokens_outside_vocab": [outside, 0],
              "sample_unfinished": [unfinished, 0]}
    done = [r for r in chosen if r.finish is not None]
    correct = miscount == 0 and outside == 0 and unfinished == 0 \
        and bool(done)
    if done and outside == 0:
        width = mix["prompt"]["max"] + mix["output"]["max"]
        width = -(-width // 128) * 128
        toks, tgt = rows(done, generated, width)
        on = tgt >= 0
        key = weights.seed_key(seed)
        served = tgt
        if control:
            x = ref.hidden(conf, key, toks, control)
            _, first = ref.head(conf, key, x, tgt, control)
            del x
            tgt = np.where(on, np.asarray(first), -1).astype(np.int32)
        x = ref.hidden(conf, key, toks)
        for prefix, t in (("", tgt),) + ((("served_", served),)
                                          if control else ()):
            gap, best = ref.head(conf, key, x, t)
            gap, best = np.asarray(gap), np.asarray(best)
            read = {"mean_gap": float(gap[on].mean()),
                    "not_best_share": float((best[on] != t[on]).mean()),
                    "widest_gap": float(gap[on].max())}
            for name, value in read.items():
                if prefix:
                    checks[prefix + name] = [value, None]
                    continue
                checks[name] = [value, limits.get(name)]
                if name in limits:
                    correct = correct and value <= limits[name]
        checks["sampled_tokens"] = [int(on.sum()), None]
    else:
        correct = False
        for name, limit in limits.items():
            checks[name] = [None, limit]
    return {"correct": correct, "checks": checks}
