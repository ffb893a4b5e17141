"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding one of those with
the longest prompt, is run through the configuration's plain reference:
each prompt with its served tokens, in one forward pass. At each served
position the gap is how far the served token's reference logit lies
below the reference's best logit there (0 where the program chose the
reference's token).

With random weights the best logit often has a near-tie, so rounding in
the served precision flips a few tokens by a small gap. A lower
precision flips more of them, by wider gaps: the mean gap grows about as
the square of the logits' error, the widest as the error itself. The
numbers compared (``numbers``) are the mean gap over every served token
of the sample and the widest gap; a cell's file gives the limit of each
number it compares.

The control (``gaps`` with a ``precision``) reads the same gaps for the
tokens that the reference computed at a lower precision puts first.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Any

import jax.numpy as jnp
import numpy as np

MAX_ROWS_TOKENS = 16384     # sequence positions per reference call


def sample(records: list[dict[str, Any]], min_tokens: int,
           seed: int) -> list[int]:
    """Indices of finished requests drawn from the seed, one of them among
    those with the longest prompt, until the sample holds ``min_tokens``
    served tokens or every request."""
    if not records:
        return []
    rng = np.random.default_rng([int(seed), 4])
    longest = max(r["prompt_len"] for r in records)
    top = [i for i, r in enumerate(records) if r["prompt_len"] == longest]
    first = int(rng.choice(top))
    rest = [i for i in rng.permutation(len(records)) if i != first]
    picked, tokens = [first], records[first]["served"].size
    for i in rest:
        if tokens >= min_tokens:
            break
        picked.append(int(i))
        tokens += records[i]["served"].size
    return sorted(picked)


def _sequence(prompt: np.ndarray, served: np.ndarray) -> np.ndarray:
    """The tokens whose last n positions predict the n served tokens."""
    return np.concatenate([prompt, served[:, :-1]], axis=1)


def _groups(pairs: list[tuple[np.ndarray, np.ndarray]]):
    """(prompts, served) stacked by shape, at most ``MAX_ROWS_TOKENS``
    positions to a group, so the reference runs few programs."""
    by_shape = defaultdict(list)
    for p, s in pairs:
        by_shape[(p.shape[1], s.shape[1])].append((p, s))
    for (t, n), items in sorted(by_shape.items()):
        rows = max(MAX_ROWS_TOKENS // (t + n), 1)
        for k in range(0, len(items), rows):
            chunk = items[k:k + rows]
            yield (np.concatenate([p for p, _ in chunk]),
                   np.concatenate([s for _, s in chunk]))


def gaps(fam: Any, params: dict, cfg: dict[str, Any],
         pairs: list[tuple[np.ndarray, np.ndarray]],
         precision: str | None = None) -> np.ndarray:
    """Reference gaps of every served token of ``pairs`` (prompt, served),
    flattened. With ``precision``, the control: the gaps of the tokens the
    reference at that precision puts first, at the same positions."""
    out = []
    for prompt, served in _groups(pairs):
        seq, n = _sequence(prompt, served), served.shape[1]
        ref = fam.last_logits(params, seq, cfg, n)
        if precision is None:
            pick = jnp.asarray(served)
        else:
            pick = jnp.argmax(fam.last_logits(params, seq, cfg, n,
                                              precision=precision), -1)
        chosen = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
        out.append(np.asarray(jnp.max(ref, -1) - chosen).ravel())
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def numbers(g: np.ndarray) -> dict[str, float]:
    """The numbers a cell may compare, from a sample's gaps."""
    if g.size == 0:         # nothing finished to compare: fails every limit
        return {"mean_gap": None, "max_gap": None}
    return {"mean_gap": float(g.mean()), "max_gap": float(g.max())}


def judge(values: dict[str, float],
          limits: dict[str, float]) -> tuple[bool, dict[str, Any]]:
    """Each limited number beside its limit, and whether all hold."""
    shown = {k: {"value": values[k], "limit": float(v)}
             for k, v in limits.items()}
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in shown.values()), shown
