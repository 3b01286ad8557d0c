"""End-to-end: the ``repro-mine`` CLI on every scheme, engine and ES
setting it offers, each mining a FIMI file through ``--input``."""

import json
import random
import sys

import pytest

from repro.core import cli
from repro.core.oracle import mine_bruteforce


def _fimi_db(seed=11, n_items=7, n_trans=40):
    rng = random.Random(seed)
    db = [sorted(i for i in range(1, n_items + 1) if rng.random() < 0.45)
          for _ in range(n_trans)]
    return [t for t in db if t]


@pytest.mark.parametrize("es", ["--es", "--no-es"])
@pytest.mark.parametrize("engine", ["oracle", "bitmap"])
@pytest.mark.parametrize("scheme", ["eclat", "declat", "adaptive", "prepost"])
def test_mining_cli(tmp_path, monkeypatch, scheme, engine, es):
    """The CLI reads a FIMI file and its ``--json-out`` map is exactly
    the brute-force oracle's, whatever path mined it."""
    db = _fimi_db()
    f = tmp_path / "db.dat"
    f.write_text("".join(" ".join(map(str, t)) + "\n" for t in db))
    out = tmp_path / "out.json"
    argv = ["repro-mine", "--input", str(f), "--minsup", "6",
            "--scheme", scheme, "--engine", engine, es,
            "--json-out", str(out)]
    if scheme == "adaptive":
        # the adaptive knobs flow through to the bitmap engine (the
        # oracle has no adaptive mode: the CLI maps it to eclat there)
        argv += ["--block-words", "1", "--diff-density", "0.3",
                 "--diff-hysteresis", "0.05"]
    monkeypatch.setattr(sys, "argv", argv)
    cli.main()

    want = {",".join(str(i) for i in sorted(s, key=str)): c
            for s, c in mine_bruteforce(db, 6).items()}
    assert any(k.count(",") >= 2 for k in want)   # reaches depth 3
    assert json.loads(out.read_text()) == want
