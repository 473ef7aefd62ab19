"""The one traffic generator: reads a traffic file's parameters and a seed.

A traffic file of kind ``"mixes"`` names workload mixes for the CMP
evaluator, each a string of application abbreviations as in the paper's
Table 2 (``"li(2)"`` is two copies).  The seed sets the order of the mixes
in the batch, so every seed runs the same work.

A traffic file of kind ``"requests"`` describes a serving queue of chat
conversations: ``tenants`` tenants, one a stream, each sending
``conversations`` conversations of ``turns`` turns.  A turn's prompt holds
the earlier turns' prompts and responses and a new prompt.  The new
prompts and the responses have the published mean lengths and no spread
(the source gives none): whole tokens, spread so that every running mean
is the published one (69.5 gives 69, 70, 69, ...).  A tenant's queue
holds its conversations in order, turn by turn; the tenants take turns in
the queue.  Every seed serves the same lengths in the same order; the seed
sets the token ids only.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator keyed by the seed and a stream of small integers;
    any whole seed works, however large."""
    return np.random.default_rng([int(seed) % 2 ** 64, *stream])


def parse_mix(spec: str, abbrev: Dict[str, str]) -> List[str]:
    apps: List[str] = []
    for tok in spec.split(","):
        tok = tok.strip()
        if "(" in tok:
            ab, count = tok[:-1].split("(")
            apps.extend([abbrev[ab]] * int(count))
        else:
            apps.append(abbrev[tok])
    return apps


def mixes(traffic: Dict, seed: int, abbrev: Dict[str, str]
          ) -> List[Tuple[str, List[str]]]:
    """``(name, apps)`` for every mix of the file, in an order set by the
    seed."""
    items = [(name, parse_mix(spec, abbrev))
             for name, spec in traffic["mixes"].items()]
    order = rng(seed, 0).permutation(len(items))
    return [items[i] for i in order]


# ----------------------------------------------------------- serving queue


@dataclasses.dataclass(frozen=True)
class Request:
    stream: int
    prompt: np.ndarray          # (prompt_len,) int32 token ids
    max_new_tokens: int


def whole(mean: float, k: int) -> int:
    """The ``k``-th of whole lengths whose running mean is ``mean``."""
    return math.floor((k + 1) * mean) - math.floor(k * mean)


def lengths(traffic: Dict) -> List[Tuple[int, int]]:
    """``(prompt_len, max_new_tokens)`` of one tenant's queue, in order."""
    out = []
    k = 0
    for _ in range(int(traffic["conversations"])):
        context = 0
        for _ in range(int(traffic["turns"])):
            prompt = context + whole(float(traffic["prompt_mean"]), k)
            response = whole(float(traffic["response_mean"]), k)
            out.append((prompt, response))
            context = prompt + response
            k += 1
    return out


def requests(traffic: Dict, seed: int, vocab_size: int) -> List[Request]:
    """The queue: the tenants' requests taken in turn, token ids drawn
    from the seed, uniform over ``[0, vocab_size)``."""
    ids = rng(seed, 2)
    return [Request(stream, ids.integers(0, vocab_size, p, dtype=np.int32),
                    r)
            for p, r in lengths(traffic)
            for stream in range(int(traffic["tenants"]))]
