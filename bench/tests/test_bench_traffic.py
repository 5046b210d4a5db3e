"""The traffic generator (bench/traffic.py): the same seed gives the
same corpus; another seed, however large, gives another corpus of the same
size."""
import numpy as np
import pytest

from bench import common, traffic


def corpus(tmp_path, name, seed, nbytes=4096):
    path = traffic.write_corpus(str(tmp_path / name), seed, nbytes)
    return np.fromfile(path, np.uint8)


def test_corpus_is_deterministic(tmp_path):
    a = corpus(tmp_path, "a.bin", 5)
    b = corpus(tmp_path, "b.bin", 5)
    c = corpus(tmp_path, "c.bin", 6)
    assert len(a) == 4096 and (a == b).all() and not (a == c).all()


@pytest.mark.parametrize("seed", [0, 2**31 - 2, 2**31 + 5, 2**40 + 1])
def test_large_seeds_give_their_own_corpus(tmp_path, seed):
    ps, nxt = common.program_seed(seed), common.program_seed(seed + 1)
    assert 0 <= ps < 2**31 and ps != nxt
    a = corpus(tmp_path, "a.bin", ps)
    assert (a == corpus(tmp_path, "b.bin", ps)).all()
    assert not (a == corpus(tmp_path, "c.bin", nxt)).all()
