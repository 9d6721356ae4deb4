"""The comparison that decides ``correct``: served greedy tokens against the
plain float32 reference.

Once the window has closed, a sample of the greedy requests the engine
finished, drawn from the seed and holding the longest of them, is run
through the reference once each: the prompt followed by the served tokens.
At each served position the reference's logits give the served token's
gap, by how much its logit lies below the reference's best. A served token
that the reference also ranks first has gap 0; a wrong cache row, position
or expert moves the served token far down the reference's ranking.

The numbers compared are those the configuration file gives a limit
(``compare.limits``): the mean gap over the sample. The widest gap is
reported beside it but has no limit: at test widths a bf16 routing flip
read as wide a gap as the precision control did (CPU), so it cannot
separate the two. The precision control (``control_gaps``) is the same
reference with every weight rounded through a lower precision, put in the
program's place: at each position the token it ranks first, read by the
float32 reference.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def sample_requests(finished: Sequence, seed: int, min_tokens: int,
                    ) -> List:
    """The longest finished request (by served tokens), then others in an
    order drawn from ``seed`` until ``min_tokens`` served tokens are held.
    ``finished`` items have ``.prompt`` and ``.output``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-len(finished[i].output), i))
    rest = order[1:]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    rng.shuffle(rest)
    out, n = [], 0
    for i in [order[0]] + rest:
        if n >= min_tokens:
            break
        out.append(finished[i])
        n += len(finished[i].output)
    return out


def _rows(req) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    prompt = np.asarray(req.prompt, np.int32)
    out = np.asarray(req.output, np.int32)
    tokens = np.concatenate([prompt, out[:-1]])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
    return tokens, rows, out


def served_gaps(model, params, cfg: dict, reqs) -> np.ndarray:
    """Gap of every served token of ``reqs`` under the float32
    reference."""
    gaps = []
    for req in reqs:
        tokens, rows, out = _rows(req)
        ref = np.asarray(model.reference_logits(params, cfg, tokens, rows))
        gaps.append(ref.max(-1) - ref[np.arange(len(out)), out])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def control_gaps(model, params, cfg: dict, reqs, weight_dtype
                 ) -> np.ndarray:
    """At the same positions, the gap of the token that the reference
    computed with weights rounded to ``weight_dtype`` ranks first."""
    gaps = []
    for req in reqs:
        tokens, rows, _ = _rows(req)
        ref = np.asarray(model.reference_logits(params, cfg, tokens, rows))
        low = np.asarray(model.reference_logits(params, cfg, tokens, rows,
                                                weight_dtype=weight_dtype))
        top = low.argmax(-1)
        gaps.append(ref.max(-1) - ref[np.arange(len(top)), top])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def numbers(gaps: np.ndarray) -> Dict[str, float]:
    if len(gaps) == 0:
        return {}
    return {"max_gap": float(gaps.max()), "mean_gap": float(gaps.mean())}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every limited number present and within its limit."""
    report = {k: {"value": values.get(k), "limit": float(lim)}
              for k, lim in limits.items()}
    ok = all(values.get(k) is not None and values[k] <= lim
             for k, lim in limits.items())
    return ok, report
