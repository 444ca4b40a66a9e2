"""SEQB traffic whose hot set drifts (Palpatine §4.2's case for online
mining): the backlog is ``seqb.py``'s, and in the window the Zipf ranks
move along the frequent sequences as the sessions go by.

Window session ``i`` that follows a frequent sequence draws its Zipf
rank ``r`` as ``seqb.py`` does, and reads sequence
``(r + drift_ranks * (i // drift_every)) % n_frequent``: every
``drift_every`` sessions the popular sequences are ``drift_ranks``
further on, and the ones that were popular fall to the tail.  Sessions
that follow no sequence are ``seqb.py``'s background reads.  Window
session ``i`` counts from the window's first session, the warm ones
included.

``build(config, mix, seed)`` returns what ``seqb.py``'s does.  As
there, the sequences and sessions and their order come from the mix's
``structure_seed``; ``seed`` only renames the blocks and draws the
values, so every seed drifts the same way.
"""

from __future__ import annotations

import copy
import importlib.util
import time
from pathlib import Path

import numpy as np


def _seqb():
    path = Path(__file__).with_name("seqb.py")
    spec = importlib.util.spec_from_file_location("bench_seqb_for_drift", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


seqb = _seqb()


def drifting(gen, rng: np.random.Generator, n: int, p_pattern: float,
             every: int, ranks: int) -> list[list]:
    """``n`` sessions of block numbers, the ranks moved by ``ranks``
    every ``every`` sessions (``gen`` is a ``seqb.SEQB``)."""
    out: list = []
    for first in range(0, n, every):
        shift = ranks * (first // every) % len(gen.sequences)
        moved = copy.copy(gen)
        moved.sequences = gen.sequences[shift:] + gen.sequences[:shift]
        out += moved.sessions(rng, min(every, n - first), p_pattern)
    return out


def build(config: dict, mix: dict, seed: int) -> dict:
    rng = np.random.default_rng(mix["structure_seed"])
    gen = seqb.SEQB(config["data"], rng)
    backlog = gen.sessions(rng, mix["backlog"]["sessions"],
                           mix["backlog"]["p_pattern"])
    w = mix["window"]
    window = drifting(gen, rng, w["sessions"], w["p_pattern"],
                      w["drift_every"], w["drift_ranks"])
    name = np.random.default_rng(seed).permutation(config["data"]["n_blocks"])
    backlog, window = ([[(seqb.key(int(name[b])), None) for b in s]
                        for s in part] for part in (backlog, window))
    t = time.perf_counter()
    data = seqb.dataset(config["data"], seed)
    return {"data": data, "data_s": time.perf_counter() - t,
            "backlog": backlog, "window": window}
