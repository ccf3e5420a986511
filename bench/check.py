"""Whether the window served what the model computes: the comparison that
decides ``correct``.

After the window, a sample drawn from the seed of the requests it
completed, the longest among them, is run once through the float32
reference of the cell's architecture module (``served_gaps`` of
``bench/archs/<a>.py``) over each prompt and the tokens served for it.
At every served position the reference's best logit minus its logit of
the token served is that token's gap; greedy decoding that computes what
the reference computes serves gaps of rounding size only.
The number compared is the widest gap over the sample.
"""

from __future__ import annotations

import numpy as np


def sample(records, seed: int, target_tokens: int, max_requests: int) -> list:
    """Completed requests: the one with the most served tokens, then others
    in an order drawn from ``seed`` until ``target_tokens`` are served."""
    done = sorted((r for r in records if r.done), key=lambda r: r.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.generated), -r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(np.random.SeedSequence([seed, 3])).permutation(len(rest))
    out, served = [longest], len(longest.generated)
    for i in order:
        if served >= target_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        served += len(rest[i].generated)
    return out


def rows(record) -> tuple[np.ndarray, np.ndarray, slice]:
    """``(sequence, next_tokens, served)``: the tokens fed, the token that
    follows each, and the rows at which a served token follows."""
    full = np.concatenate([record.spec.prompt,
                           np.asarray(record.generated, np.int32)])
    n = len(record.spec.prompt)
    return full[:-1], full[1:], slice(n - 1, len(full) - 1)


def widest_gap(arch, cfg: dict, params, records, length: int) -> tuple[float, int]:
    """``(widest gap, tokens compared)`` of the served tokens of
    ``records`` under architecture module ``arch``; each sequence is
    padded to ``length`` rows."""
    worst, count = 0.0, 0
    for r in records:
        seq, nxt, served = rows(r)
        gaps = np.asarray(arch.served_gaps(cfg, params, seq, nxt, length))
        worst = max(worst, float(gaps[served].max()))
        count += served.stop - served.start
    return worst, count


def control_gap(arch, cfg: dict, params, records, length: int) -> float:
    """The widest gap of the tokens the fp8 control puts first at the same
    positions: what the number compared reads when the model is computed
    one precision step below the one it states."""
    worst = 0.0
    for r in records:
        seq, _, served = rows(r)
        gaps = np.asarray(arch.control_gaps(cfg, params, seq, length))
        worst = max(worst, float(gaps[served].max()))
    return worst
