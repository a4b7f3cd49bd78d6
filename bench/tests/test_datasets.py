"""Dataset generators are found by the configuration's `generator` and
given the whole `dataset` entry; `kat7` makes the same bytes as the
generator it was copied from."""
import hashlib

import numpy as np
import pytest

from harness import datagen
from harness.cells import BENCH, load_cell, make_dataset

KAT7 = {"generator": "kat7", "rows": 90000, "features": 9, "classes": 2}

# sha256 of X and y for three of the benchmark's run seeds, as the
# generator made them before it moved to bench/datasets/
PINNED = {
    1618033989: ("16353ad15d59c2432b8d3146b39841a749fbb8f5fb1e0a93d7ac9c5f0171129c",
                 "44ca0eab09512c97d8c5cf26d89df3cbdb9f748079563ec8fe04a25c3a9db82c"),
    2047483653: ("d7139f7c96b7251efa48af6b2e8230fbd6e92d94a818d8bf16bf54ae85624f18",
                 "ca29daa60cf3648511c96faa9a8472fab42bb79d732c8084b1d5836397af7d81"),
    1000000007: ("3164ce41f9ed66dfed41382c8ed05d97dd67d7bd96b6e9d4350c9d329530972d",
                 "788add64d77f7f548534db01710fd86ac286f48a93cd366f1c1a42858221ae36"),
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_kat7_bytes_are_pinned(seed):
    data_seed, _ = datagen.seeds(seed, 2)
    X, y = make_dataset(KAT7, data_seed)
    assert X.dtype == y.dtype == np.float32
    assert (hashlib.sha256(X.tobytes()).hexdigest(),
            hashlib.sha256(y.tobytes()).hexdigest()) == PINNED[seed]


def test_cell_dataset_is_the_pinned_shape():
    assert load_cell("kat7-90k.tree-fit").config["dataset"] == KAT7


def test_unknown_generator_names_its_path():
    with pytest.raises(FileNotFoundError) as e:
        make_dataset({**KAT7, "generator": "no-such-set"}, 1)
    assert str(BENCH / "datasets" / "no-such-set.py") in str(e.value)


@pytest.mark.parametrize("shape, error", [({"skew": 0.5}, TypeError),
                                          ({"classes": 3}, ValueError)])
def test_a_shape_the_generator_does_not_make_is_an_error(shape, error):
    """A key it does not take, and a class count it cannot label."""
    with pytest.raises(error):
        make_dataset({**KAT7, "rows": 64, **shape}, 1)


def test_data_of_another_shape_than_stated_is_an_error(tmp_path):
    folder = tmp_path / "bench" / "datasets"
    folder.mkdir(parents=True)
    (folder / "narrow.py").write_text(
        "import numpy as np\n\n\n"
        "def make(seed, rows, features):\n"
        "    return np.zeros((rows, 2), np.float32), np.zeros(rows, np.float32)\n")
    with pytest.raises(ValueError, match="states"):
        make_dataset({"generator": "narrow", "rows": 4, "features": 3}, 1,
                     tmp_path)
