"""The one traffic generator: reads a mix's parameters, draws from a seed.

Every seed gets the same work in another order, so that runs with
different seeds measure the same thing:

- request mixes come in blocks of ``block`` requests holding exactly
  ``weight * block`` requests of each class, shuffled; each class walks
  its corpus round-robin from a seeded start;
- open-loop arrivals take, in each block, the same gaps (the midpoint
  quantiles of an exponential with the mix's rate, so the block's mean
  gap is exactly ``1 / rate``), shuffled;
- camera frames replay a ring of consecutive frames forward and back
  (``pingpong``: motion stays continuous) or forward only (``cycle``),
  each frame held ``hold`` times.
"""

from __future__ import annotations

import numpy as np


def block_classes(mix: dict, block: int) -> list[str]:
    counts = {c: round(w * block) for c, w in sorted(mix.items())}
    if sum(counts.values()) != block:
        raise ValueError(f"mix {mix} does not split a block of {block} into whole requests")
    return [c for c, n in counts.items() for _ in range(n)]


def block_gaps(rate: float, block: int) -> np.ndarray:
    u = (np.arange(block) + 0.5) / block
    gaps = -np.log1p(-u)
    return gaps * (block / rate / gaps.sum())


def requests(traffic: dict, corpus_sizes: dict, seed: int):
    """Endless ``(class, corpus_index, gap_s)`` triples; ``gap_s`` is the
    time since the previous arrival (0 for a closed loop)."""
    rng = np.random.default_rng((seed, 2))
    block = traffic["block"]
    classes = block_classes(traffic["mix"], block)
    rate = traffic.get("rate_per_s")
    gaps = block_gaps(rate, block) if rate else np.zeros(block)
    nxt = {c: int(rng.integers(corpus_sizes[c])) for c in sorted(corpus_sizes)}
    while True:
        order = rng.permutation(block)
        gap_order = rng.permutation(block)
        for k in range(block):
            c = classes[order[k]]
            idx = nxt[c]
            nxt[c] = (idx + 1) % corpus_sizes[c]
            yield c, idx, float(gaps[gap_order[k]])


def open_loop(traffic: dict, corpus_sizes: dict, seed: int, seconds: float):
    """The arrivals due in ``[0, seconds)``: ``(due_s, class, index)``."""
    out, t = [], 0.0
    for c, idx, gap in requests(traffic, corpus_sizes, seed):
        t += gap
        if t >= seconds:
            return out
        out.append((t, c, idx))


def replay_index(s: int, traffic: dict) -> int:
    """Ring index of the ``s``-th frame of a camera mix."""
    n = traffic["ring"]
    step = s // traffic.get("hold", 1)
    if traffic["replay"] == "cycle":
        return step % n
    period = 2 * (n - 1)
    m = step % period
    return m if m < n else period - m

