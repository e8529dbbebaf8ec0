"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the window's finished requests, drawn from the seed and always holding the
longest, is run through the plain float32 reference (``bench/reference``),
teacher-forced over each prompt with its served tokens.  At every served
position the reference's best logit is compared with its logit of the token
served there; the widest of these gaps over the sample (``widest_gap``) is
the number compared with the cell's limit.  A request of the window that
never finished fails the check too (``verdict``).

The control (``control_gaps``) puts the reference itself in the program's
place, computed in a lower precision: at the same positions it reads the
gap of the token that the lower precision puts first.
"""

from __future__ import annotations

import numpy as np

from bench.lib.traffic import prompt_tokens


def widest_gap(g: np.ndarray) -> float | None:
    """The number compared with the cell's limit; None where nothing was
    served, which is not correct."""
    return float(g.max()) if g.size else None


def verdict(gap: float | None, unfinished: int, limit: float) -> bool:
    """``correct``: the widest gap within the limit and every request finished."""
    return gap is not None and gap <= limit and unfinished == 0


def sample(records, seed: int, min_tokens: int, max_requests: int) -> list:
    """Finished records: the longest (prompt + output), then others in an
    order drawn from the seed, until ``min_tokens`` served tokens."""
    done = [r for r in records if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.req.prompt_len + r.req.n_out, -r.req.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) % (1 << 64), 0x5A3E])
    out, n = [longest], longest.req.n_out
    for i in rng.permutation(len(rest)):
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += rest[i].req.n_out
    return out


def sequences(recs, served: list[list[int]], vocab: int, seed: int):
    """Teacher-forced inputs and the rows that predict each served token."""
    seqs, rows = [], []
    for r, toks in zip(recs, served):
        p = prompt_tokens(r.req, vocab, seed)
        seqs.append(np.concatenate([p, np.asarray(toks[:-1], np.int32)]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(toks)))
    return seqs, rows


def gaps(ref_logits: list[np.ndarray], chosen: list[np.ndarray]) -> np.ndarray:
    """Reference best logit minus its logit of the chosen token, per position."""
    out = [lg.max(axis=1) - lg[np.arange(len(c)), c] for lg, c in zip(ref_logits, chosen)]
    return np.concatenate(out) if out else np.zeros(0)


def served_gaps(reference, seed: int, recs, served, vocab: int) -> np.ndarray:
    seqs, rows = sequences(recs, served, vocab, seed)
    ref = reference.logits(seed, seqs, rows)
    return gaps(ref, [np.asarray(t, np.int64) for t in served])


def control_gaps(reference, control, seed: int, recs, served, vocab: int) -> np.ndarray:
    seqs, rows = sequences(recs, served, vocab, seed)
    ref = reference.logits(seed, seqs, rows)
    low = control.logits(seed, seqs, rows)
    return gaps(ref, [lg.argmax(axis=1) for lg in low])
