"""The one traffic generator. A cell's file gives the parameters; this turns
them and --seed into the requests of a run.

Every seed replays ONE trace of arrivals and request shapes (system prompt,
user turn length, answer length), drawn once from the cell's own
`schedule_seed`; --seed decides the token values (and, in the replica, the
weights). A window holds a hundred-odd requests: over so few, a trace drawn
or reordered by the seed makes every metric measure the draw (PERF.md, PR
23: the same shapes and gaps in an order permuted by the seed spread the
tokens per second by 17 % and the 95th percentile of the first-token time
by 24 %), while over one trace the spread between runs is the system's.
The trace also depends on the window's length: the number of requests is
part of its seed."""

from __future__ import annotations

import math

import numpy as np


def _clipped_lognormal(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_gaps(rng, kind: str, n: int, seconds: float) -> np.ndarray:
    """n+1 gaps that sum to `seconds`: arrival i is the sum of the first
    i+1. For `poisson` they are the spacings of a Poisson process that is
    known to have n arrivals in the window (exponentials, normalised)."""
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    g = rng.exponential(1.0, size=n + 1)
    return g * (seconds / g.sum())


def build_schedule(cell: dict, vocab: int, seed: int, seconds: float,
                   rate: float | None = None) -> dict:
    """-> {"system_prompts": [[tok...]...], "requests": [{"due_s", "sys",
    "user_len", "max_new_tokens", "tokens"}...]} sorted by due time."""
    rate = float(cell["rate_per_s"] if rate is None else rate)
    n = max(1, int(math.floor(rate * seconds)))
    fixed = np.random.default_rng([int(cell["schedule_seed"]), n])
    sp = cell["system_prompts"]
    ranks = np.arange(1, len(sp["lengths"]) + 1, dtype=np.float64)
    p = ranks ** -float(sp["zipf_s"])
    sys_idx = fixed.choice(len(ranks), size=n, p=p / p.sum())
    user_len = _clipped_lognormal(fixed, cell["user_turn"], n)
    max_new = _clipped_lognormal(fixed, cell["max_new_tokens"], n)
    gaps = arrival_gaps(fixed, cell["arrivals"], n, float(seconds))
    due = np.cumsum(gaps)[:n]

    tok = np.random.default_rng([int(seed), 2])
    systems = [tok.integers(1, vocab, size=int(L)).tolist()
               for L in sp["lengths"]]
    requests = []
    for i in range(n):
        s, ul = int(sys_idx[i]), int(user_len[i])
        requests.append({
            "due_s": float(due[i]), "sys": s, "user_len": ul,
            "max_new_tokens": int(max_new[i]),
            "tokens": systems[s] + tok.integers(1, vocab, size=ul).tolist(),
        })
    return {"system_prompts": systems, "requests": requests}
