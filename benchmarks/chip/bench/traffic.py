"""The one traffic generator: reads a traffic file's parameters and a seed.

A traffic file of kind ``"mixes"`` names workload mixes for the CMP
evaluator, each a string of application abbreviations as in the paper's
Table 2 (``"li(2)"`` is two copies).  The seed sets the order of the mixes
in the batch, so every seed runs the same work.
"""
from __future__ import annotations

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
