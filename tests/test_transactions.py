"""Transaction generators: determinism and statistical regimes of the
dataset replicas and the Quest generator."""

from repro.data import make_dataset, DATASET_REPLICAS
from repro.data.transactions import gen_quest


def test_datasets_deterministic():
    for name in ("t40-like", "chess-like"):
        a, ma = make_dataset(name, seed=3)
        b, mb = make_dataset(name, seed=3)
        assert a == b and ma == mb
        c, _ = make_dataset(name, seed=4)
        assert a != c


def test_dataset_regimes():
    """Dense replicas: fixed-length transactions (one item per column);
    sparse replicas: variable-length."""
    dense, _ = make_dataset("chess-like")
    lens = {len(t) for t in dense}
    assert len(lens) == 1
    sparse, _ = make_dataset("retail-like")
    assert len({len(t) for t in sparse}) > 5


def test_all_replicas_generate():
    for name in DATASET_REPLICAS:
        db, minsups = make_dataset(name)
        assert len(db) > 100
        assert len(minsups) == 4
        assert minsups == sorted(minsups)


def test_quest_items_sorted_unique():
    db = gen_quest(n_trans=100, seed=1)
    for t in db:
        assert t == sorted(set(t))
