"""Whether what the timed path served is correct.

After the window closes and the program's state is freed, a sample of the
requests it finished, drawn from the seed and always holding the longest,
goes through the plain reference once: each prompt followed by the tokens
the program served. At each served position the reference's logit of the
served token is compared with its best logit there. The number judged is
the widest such gap over the sample: greedy decoding should serve the
reference's best token, or one within rounding of it.

The control puts the reference in the program's place at a lower
precision (float8 weights): at the same positions it reads the gap of the
token the lower precision puts first. It must come out not correct.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

MIN_TOKENS = 256        # served tokens the sample holds at least,
MIN_REQUESTS = 8        # and requests, so that it spans the batch,
MAX_POSITIONS = 40960   # while the reference runs at most this many positions

Item = Tuple[List[int], List[int]]        # prompt, served tokens


def sample(recs: Sequence, seed: int) -> List[Item]:
    """The longest finished request, then others in an order drawn from
    ``seed`` until the sample holds ``MIN_REQUESTS`` requests serving
    ``MIN_TOKENS`` tokens; one that would take the sample past
    ``MAX_POSITIONS`` positions is passed over for the next. Requests
    still in flight at the close (their tokens so far) are drawn only
    after every finished one."""
    done = [r for r in recs if r.finished]
    rest = [r for r in recs
            if not r.finished and r.handle is not None and len(r.tokens) > 1]
    rng = np.random.default_rng(seed)
    if not done and not rest:
        return []
    pool = done or rest
    longest = max(pool, key=lambda r: len(r.req.prompt) + len(r.tokens))
    order = [longest] + [r for i in rng.permutation(len(done))
                         if (r := done[i]) is not longest]
    order += [rest[i] for i in rng.permutation(len(rest))
              if rest[i] is not longest]
    out: List[Item] = []
    served = positions = 0
    for r in order:
        if served >= MIN_TOKENS and len(out) >= MIN_REQUESTS:
            break
        n = len(r.req.prompt) + len(r.tokens) - 1
        if out and positions + n > MAX_POSITIONS:
            continue
        out.append((list(r.req.prompt), r.tokens))
        served += len(r.tokens)
        positions += n
    return out


def rows(item: Item) -> Tuple[List[int], List[int], List[int]]:
    """The sequence the reference reads, the positions whose logits it
    needs, and the served token each of them predicts."""
    prompt, served = item
    p = len(prompt)
    return prompt + served[:-1], list(range(p - 1, p - 1 + len(served))), \
        served


def served_gaps(ref, items: Sequence[Item], control: bool = False
                ) -> Dict[str, float]:
    """The widest gap of the served tokens (and, with ``control``, of the
    tokens float8 weights put first) below the reference's best logit."""
    seqs, want, toks = zip(*(rows(it) for it in items))
    best = ref.logits(seqs, want)
    out = {"tokens": float(sum(len(t) for t in toks)),
           "widest_gap": max(float(np.max(lg.max(-1) - lg[np.arange(len(t)),
                                                           t]))
                             for lg, t in zip(best, toks))}
    if control:
        low = ref.logits(seqs, want, quant="fp8")
        out["control_gap"] = max(
            float(np.max(lg.max(-1) - lg[np.arange(len(lo)), lo.argmax(-1)]))
            for lg, lo in zip(best, low))
    return out
