"""SEQB traffic (Palpatine §5): blocks read in sessions that follow one of
a Zipf-ranked set of frequent sequences, or log-uniform background reads.

A copy of the repository's ``benchmarks.workloads.SEQB`` generator, kept
here so the benchmark's traffic cannot change with the program.  Two
departures, neither of which changes the distribution: the per-session
draws are made in bulk, and every block's value is built from the seed
and carries its block number, so a read of the wrong key shows.

``build(config, mix, seed)`` returns the data set, the logged backlog
and the window's sessions.  A session is a list of ``(key, None)`` reads.
The sequences and sessions, and their order, are drawn from the mix's
``structure_seed``; ``seed`` only renames the blocks (a permutation) and
draws the values.  So every seed mines the same sizes, compiles the same
programs and serves the same sessions in the same order, on other keys.
"""

from __future__ import annotations

import time

import numpy as np

VALUE_CHUNK = 100_000


def key(block: int) -> tuple:
    return ("blocks", f"b{block}", "d")


def dataset(data: dict, seed: int) -> dict:
    """Every block's value: its block number (8 bytes, little-endian),
    then ``block_bytes - 8`` filler bytes drawn from the seed, the last of
    them odd (numpy's fixed-width bytes drop trailing zeros)."""
    n, width = data["n_blocks"], data["block_bytes"]
    filler = np.random.default_rng([seed, 1]).integers(
        0, 256, width, dtype=np.uint8)
    filler[-1] |= 1
    values: list = []
    for lo in range(0, n, VALUE_CHUNK):
        hi = min(n, lo + VALUE_CHUNK)
        rows = np.empty((hi - lo, width), np.uint8)
        rows[:] = filler
        rows[:, :8] = np.arange(lo, hi, dtype="<u8").view(np.uint8).reshape(
            hi - lo, 8)
        values += rows.view(f"S{width}").ravel().tolist()
    return dict(zip(map(key, range(n)), values))


class SEQB:
    def __init__(self, data: dict, rng: np.random.Generator):
        self.n_blocks = data["n_blocks"]
        self.min_seq, self.max_seq = data["min_seq"], data["max_seq"]
        self.sequences = [
            [int(b) for b in rng.choice(
                self.n_blocks,
                size=int(rng.integers(self.min_seq, self.max_seq + 1)),
                replace=False)]
            for _ in range(data["n_frequent"])
        ]
        ranks = np.arange(1, data["n_frequent"] + 1, dtype=np.float64)
        w = ranks ** (-data["zipf_exp"])
        self.seq_probs = w / w.sum()

    def sessions(self, rng: np.random.Generator, n: int,
                 p_pattern: float) -> list[list]:
        """``n`` sessions of block numbers: with probability ``p_pattern``
        a frequent sequence drawn by its Zipf rank, else 3-10 background
        blocks whose popularity is log-uniform (paper: "some data
        containers are accessed more often than others")."""
        pattern = rng.random(n) < p_pattern
        idx = rng.choice(len(self.sequences), size=n, p=self.seq_probs)
        sizes = rng.integers(self.min_seq, self.max_seq + 1, size=n)
        out = []
        for s in range(n):
            if pattern[s]:
                blocks = self.sequences[int(idx[s])]
            else:
                u = rng.random(int(sizes[s]))
                blocks = [max(int(self.n_blocks ** x) - 1, 0) for x in u]
            out.append(blocks)
        return out


def build(config: dict, mix: dict, seed: int) -> dict:
    rng = np.random.default_rng(mix["structure_seed"])
    gen = SEQB(config["data"], rng)
    backlog = gen.sessions(rng, mix["backlog"]["sessions"],
                           mix["backlog"]["p_pattern"])
    window = gen.sessions(rng, mix["window"]["sessions"],
                          mix["window"]["p_pattern"])
    name = np.random.default_rng(seed).permutation(config["data"]["n_blocks"])
    backlog, window = ([[(key(int(name[b])), None) for b in s] for s in part]
                       for part in (backlog, window))
    t = time.perf_counter()
    data = dataset(config["data"], seed)
    return {"data": data, "data_s": time.perf_counter() - t,
            "backlog": backlog, "window": window}
