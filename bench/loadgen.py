"""One general traffic generator, driven by a mix file.

A mix (``bench/traffic/<name>.json``) holds only parameters.  Its sizes
(the system prompts' lengths, and each request's system prompt, user turn
and output length) and, for an open loop, its arrival times are drawn once
from the mix's own ``shape_seed``: a fixed schedule.  The run's ``--seed``
draws the token contents only.  So every seed serves the same work in the
same shapes at the same times, and what one seed compiles the next finds
in the cache.  (Which request takes which arrival is fixed too: on one
chip, a seed that only reordered the schedule moved the p95 of TTFT by
far more than two runs of one seed differ.)  Each quantity is drawn from a stream of its
own, so a multiset of ``n`` requests is the first ``n`` of any larger one:
a rate sweep that warms up its largest multiset has warmed up the others.

Two loops:

* ``open``: ``round(rate * seconds)`` requests due at the sorted points of
  that many uniform draws over the window (a Poisson process conditioned
  on its count).
* ``closed``: ``clients`` clients, each sending its next request when the
  last completes.  Each client's queue holds more requests than a window
  can finish.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

# token ids are drawn from [1, vocab): 0 is the engine's pad token
FIRST_TOKEN = 1


@dataclasses.dataclass
class RequestSpec:
    """One request: what is sent and when it is due (seconds after the
    window opens; ``None`` for a closed loop, where it is due when its
    client's previous request completes)."""

    index: int
    prompt: np.ndarray               # int32 token ids
    max_new_tokens: int
    due: float | None = None
    client: int | None = None
    group: int | None = None         # shared system prompt, if any
    shared_len: int = 0              # tokens of that system prompt


def lognormal_lengths(rng: np.random.Generator, n: int, spec: dict) -> np.ndarray:
    """``n`` lengths, lognormal around ``spec["median"]`` with log-sd
    ``spec["sigma"]``, rounded and clipped to ``[spec["min"], spec["max"]]``."""
    raw = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def zipf_choice(rng: np.random.Generator, n: int, k: int, s: float) -> np.ndarray:
    """``n`` draws of ranks ``0..k-1`` with probability proportional to
    ``1 / (rank + 1) ** s``."""
    w = 1.0 / np.arange(1, k + 1) ** s
    return rng.choice(k, size=n, p=w / w.sum())


def request_count(mix: dict, seconds: float) -> int:
    """How many requests a run of ``seconds`` draws from the multiset."""
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    return mix["clients"] * mix["requests_per_client"]


def _streams(mix: dict) -> dict[str, np.random.Generator]:
    """One generator per drawn quantity, all from the mix's ``shape_seed``."""
    names = ("system", "group", "user", "output", "arrival")
    kids = np.random.SeedSequence(mix["shape_seed"]).spawn(len(names))
    return {k: np.random.default_rng(s) for k, s in zip(names, kids)}


def system_lengths(mix: dict) -> list[int]:
    """The lengths of the mix's shared system prompts (none if it shares
    none)."""
    shared = mix.get("shared_prefix")
    if shared is None:
        return []
    rng = _streams(mix)["system"]
    return lognormal_lengths(rng, shared["count"], shared["length"]).tolist()


def shapes(mix: dict, seconds: float) -> list[tuple[int, int, int, int]]:
    """The mix's fixed multiset of ``(group, shared_len, user_len, out_len)``
    rows (group -1 and shared_len 0 where nothing is shared), independent
    of the run's seed."""
    n = request_count(mix, seconds)
    rng = _streams(mix)
    user = lognormal_lengths(rng["user"], n, mix["prompt"])
    out = lognormal_lengths(rng["output"], n, mix["output"])
    lengths = system_lengths(mix)
    if not lengths:
        return [(-1, 0, int(u), int(o)) for u, o in zip(user, out)]
    groups = zipf_choice(rng["group"], n, len(lengths), mix["shared_prefix"]["zipf_s"])
    return [(int(g), int(lengths[g]), int(u), int(o))
            for g, u, o in zip(groups, user, out)]


def arrivals(mix: dict, seconds: float) -> np.ndarray:
    """An open loop's due times, seconds after the window opens, sorted."""
    n = request_count(mix, seconds)
    return np.sort(_streams(mix)["arrival"].uniform(0.0, seconds, n))


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[RequestSpec]:
    """The run's requests, in the order they are sent (by due time for an
    open loop; by client, then turn, for a closed loop)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    systems = [rng.integers(FIRST_TOKEN, vocab, L, dtype=np.int32)
               for L in system_lengths(mix)]
    specs = []
    for i, (g, L, u, o) in enumerate(shapes(mix, seconds)):
        turn = rng.integers(FIRST_TOKEN, vocab, u, dtype=np.int32)
        prompt = np.concatenate([systems[g], turn]) if g >= 0 else turn
        specs.append(RequestSpec(index=i, prompt=prompt, max_new_tokens=o,
                                 group=g if g >= 0 else None, shared_len=L))
    if mix["loop"] == "open":
        for s, t in zip(specs, arrivals(mix, seconds)):
            s.due = float(t)
    else:
        c = mix["clients"]
        for s in specs:
            s.client = s.index % c
    return specs


def pow2_at_least(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, n))))
