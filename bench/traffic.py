"""Inputs made from the seed: the training corpus. One general generator
per kind reads the traffic file's parameters; a new mix is a new data
file."""
from __future__ import annotations

import os

import numpy as np


def write_corpus(path: str, seed: int, nbytes: int) -> str:
    """Random bytes from the seed, for the trainer's byte reader."""
    data = np.random.default_rng([seed, 0xC0]).integers(
        0, 256, nbytes, dtype=np.uint8)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    data.tofile(tmp)
    os.replace(tmp, path)
    return path
