"""Shared output helper of the paper-quality reproductions."""
from __future__ import annotations


def report(name: str, value, unit: str, derived: str = ""):
    print(f"{name},{value:.6g},{unit},{derived}")
