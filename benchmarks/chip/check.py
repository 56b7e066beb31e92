"""Is what the timed path served correct?

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest one, goes through the plain
reference (the model family's, ``families/``, with ``reference.py``)
with its prompt and the tokens it was served.  The number compared is
the widest gap by which a served token's reference logit lies below the
reference's best logit at that position: greedy decoding at the
configuration's precision keeps it near zero, and a lower precision or a
wrong token opens it.

Every finished request must also hold exactly the tokens it asked for,
each a vocabulary id (``bad_requests``, limit 0).
"""

from __future__ import annotations

import numpy as np

import reference

ROW_ALIGN = 128


def sample(window, k: int, seed: int) -> list:
    """``k`` finished requests: the longest, and the rest drawn from the
    seed."""
    done = [s for s in window.sent if s.handle.done]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (
        done[i].req.prompt.size + len(done[i].handle.tokens), -i))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def bad_requests(window, vocab: int) -> int:
    bad = 0
    for s in window.sent:
        if not s.handle.done:
            continue
        toks = np.asarray(s.handle.tokens)
        if (toks.size != s.req.max_new_tokens or toks.min() < 0
                or toks.max() >= vocab):
            bad += 1
    return bad


def batch(chosen: list, k: int, max_len: int, max_out: int):
    """Reference inputs at fixed shapes (one compile per cell): tokens
    (k, T), the flat rows whose logits chose a served token, and those
    tokens, padded by repeating the first row."""
    t = -(-max_len // ROW_ALIGN) * ROW_ALIGN
    tokens = np.zeros((k, t), np.int32)
    rows, served = [], []
    for b, s in enumerate(chosen):
        p, out = s.req.prompt, np.asarray(s.handle.tokens, np.int32)
        seq = np.concatenate([p, out[:-1]])
        tokens[b, :seq.size] = seq
        rows += [b * t + p.size - 1 + j for j in range(out.size)]
        served += out.tolist()
    n = k * max_out
    rows += [rows[0]] * (n - len(rows))
    served += [served[0]] * (n - len(served))
    return tokens, np.asarray(rows, np.int32), np.asarray(served, np.int32)


def logit_gaps(family, tree, sizes: dict, chosen: list, mix: dict, k: int,
               control: bool = False) -> dict:
    """Widest reference-logit gap of the served tokens (``program``) and,
    with ``control``, of the fp8 control's first choices, over the same
    prompts and served tokens, by ``family``'s reference; plus how many
    tokens were compared."""
    tokens, rows, served = batch(chosen, k, mix["max_len"],
                                 mix["output_len"]["max"])
    g = reference.gaps(family, tree, sizes, tokens, rows, served,
                       control=control)
    out = {name: float(np.max(np.asarray(v))) for name, v in g.items()}
    out["tokens"] = int(sum(len(s.handle.tokens) for s in chosen))
    return out
