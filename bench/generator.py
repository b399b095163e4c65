"""The one generator of the benchmark's traffic, driven by the data files
under ``bench/traffic/``.

A training mix (``"kind": "train"``, run by ``bench/train.py``) is a job
manifest: batch, sequence, checkpoint interval, optimizer schedule. Its
rows are drawn here, from the seed and the step, uniformly over the
vocabulary, so that every row of every step differs. A mix of another
``kind`` is run by ``bench/<kind>.py``.
"""

from __future__ import annotations

import json
import zlib
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def sub_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for one use of the run's seed, which may be larger
    than 32 bits hold."""
    ss = np.random.SeedSequence([seed % 2**64, zlib.crc32(tag.encode())])
    return int(ss.generate_state(1)[0] >> 1)


# -- training ---------------------------------------------------------------

def train_rows(traffic: dict, seed: int, vocab: int, step: int) -> dict:
    """The rows of one training step: tokens and their next tokens."""
    rng = np.random.default_rng(
        np.random.SeedSequence([sub_seed(seed, "rows"), step]))
    seq = rng.integers(0, vocab, (traffic["batch"], traffic["seq"] + 1))
    return {"tokens": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:].astype(np.int32)}


class TrainFeed:
    """The learner's data feed: ``batch_at(step)`` as the program's own
    feed has it, with rows from this generator. ``drawn`` lists the steps
    whose rows the program took, so a run can see that it took them."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.traffic, self.seed, self.vocab = traffic, seed, vocab
        self.drawn: list[int] = []

    def batch_at(self, step: int) -> dict:
        self.drawn.append(step)
        return train_rows(self.traffic, self.seed, self.vocab, step)
