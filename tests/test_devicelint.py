"""devicelint fixture tests (ISSUE 10): every rule fires on a known-bad
snippet, annotation suppression works at both grammars (comment and
``host_sync`` escape), the baseline ratchet fails on new AND stale
entries, and the runtime guard half (``core/guards.py``) arms/disarms
the JAX d2h transfer guard exactly where the annotations say.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tools.devicelint.engine import (  # noqa: E402
    REPO, diff_baseline, lint_paths, lint_source, load_baseline,
    save_baseline,
)

CORE = "src/repro/core/snippet.py"


def codes(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# DL001 — host-sync
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    "import numpy as np\nx = np.asarray(rows)\n",
    "import numpy as np\nx = np.array(rows)\n",
    "import jax\nx = jax.device_get(rows)\n",
    "y = rows.block_until_ready()\n",
    "n = counts.item()\n",
    "import jax.numpy as jnp\nn = int(jnp.sum(x))\n",
    "import jax.numpy as jnp\nn = float(jnp.max(x))\n",
    "import jax.numpy as jnp\nif jnp.any(x):\n    pass\n",
    "import jax.numpy as jnp\nwhile jnp.all(x):\n    pass\n",
])
def test_dl001_fires_on_known_bad(bad):
    assert "DL001" in codes(lint_source(bad, rel=CORE, only={"DL001"}))


@pytest.mark.parametrize("ok", [
    # annotated on the line above
    "import numpy as np\n# host-sync: pack-time host list\n"
    "x = np.asarray(rows)\n",
    # annotated on the same line
    "import numpy as np\nx = np.asarray(rows)  # host-sync: host list\n",
    # annotation covers a multi-line statement
    "import numpy as np\n# host-sync: host metadata\n"
    "x = np.concatenate([np.asarray(a)\n                    for a in r])\n",
    # PR 7 grammar still counts
    "import numpy as np\n# HOST-SYNC (load-bearing): audited readback\n"
    "x = np.asarray(rows)\n",
    # the runtime escape doubles as the annotation
    "import numpy as np\nwith host_sync('accounting readback'):\n"
    "    x = np.asarray(rows)\n",
    # int()/float() on plain python values is not a sync
    "n = int(len(rows))\n",
    # branching on host values is fine
    "if len(rows) > 2:\n    pass\n",
])
def test_dl001_suppression_and_negatives(ok):
    assert lint_source(ok, rel=CORE, only={"DL001"}) == []


def test_dl001_annotation_requires_a_why():
    bad = "import numpy as np\n# host-sync:\nx = np.asarray(rows)\n"
    assert "DL001" in codes(lint_source(bad, rel=CORE, only={"DL001"}))


def test_dl001_scope_is_core_and_kernels_only():
    bad = "import numpy as np\nx = np.asarray(rows)\n"
    for exempt in ("src/repro/launch/mesh.py", "tests/test_x.py",
                   "src/repro/core/oracle.py", "src/repro/core/cli.py"):
        assert lint_source(bad, rel=exempt, only={"DL001"}) == []


def test_removing_an_annotation_fails_devicelint():
    """Regression (ISSUE 10 satellite): strip one real `# host-sync:`
    annotation from core/rowstore.py and the file must stop linting
    clean."""
    path = REPO / "src/repro/core/rowstore.py"
    text = path.read_text(encoding="utf-8")
    rel = "src/repro/core/rowstore.py"
    assert lint_source(text, rel=rel, only={"DL001"}) == []
    lines = [ln for ln in text.splitlines(keepends=True)
             if "host-sync: host extent-table lookup" not in ln]
    assert len(lines) < len(text.splitlines())  # the annotation exists
    broken = lint_source("".join(lines), rel=rel, only={"DL001"})
    assert "DL001" in codes(broken)


# ---------------------------------------------------------------------------
# DL002 — ref-pinning (cross-file fixtures)
# ---------------------------------------------------------------------------

OPS_REL = "src/repro/kernels/ops.py"
REF_REL = "src/repro/kernels/ref.py"


def lint_ops(ops_src, ref_src, test_src=None):
    extra = {REF_REL: ref_src}
    if test_src is not None:
        extra["tests/test_x.py"] = test_src
    return lint_source(ops_src, rel=OPS_REL, only={"DL002"}, extra=extra)


def test_dl002_missing_twin_fires():
    out = lint_ops("def my_op(x):\n    return x\n", "def other_ref(x):\n    return x\n")
    assert codes(out) == ["DL002"] and "my_op" in out[0].message


def test_dl002_missing_test_reference_fires():
    out = lint_ops("def my_op(x):\n    return x\n",
                   "def my_op_ref(x):\n    return x\n",
                   "def test_nothing():\n    pass\n")
    assert codes(out) == ["DL002"] and "unverified" in out[0].message


def test_dl002_clean_when_pinned_and_tested():
    out = lint_ops("def my_op(x):\n    return x\n",
                   "def my_op_ref(x):\n    return x\n",
                   "from ops import my_op\nfrom ref import my_op_ref\n")
    assert out == []


def test_dl002_factory_and_docstring_resolution():
    ops_src = (
        "def make_my_op(mesh):\n    return None\n\n"
        "def oddly_named(x):\n    '''Pinned by ``special_ref``.'''\n"
        "    return x\n")
    ref_src = ("def my_op_ref(x):\n    return x\n\n"
               "def special_ref(x):\n    return x\n")
    test_src = ("make_my_op my_op_ref oddly_named special_ref\n")
    assert lint_ops(ops_src, ref_src, test_src) == []


def test_dl002_private_defs_ignored():
    assert lint_ops("def _impl(x):\n    return x\n", "") == []


# ---------------------------------------------------------------------------
# DL003 — retrace hazards
# ---------------------------------------------------------------------------

def test_dl003_jit_in_loop_fires():
    bad = ("import jax\nfor i in range(3):\n"
           "    f = jax.jit(lambda x: x)\n")
    assert "DL003" in codes(lint_source(bad, rel="src/repro/m.py",
                                        only={"DL003"}))


def test_dl003_jit_in_uncached_function_fires():
    bad = ("import jax\ndef build():\n"
           "    return jax.jit(lambda x: x)\n")
    assert "DL003" in codes(lint_source(bad, rel="src/repro/m.py",
                                        only={"DL003"}))


def test_dl003_lru_cached_factory_is_clean():
    ok = ("import functools, jax\n"
          "@functools.lru_cache(maxsize=None)\n"
          "def build():\n    return jax.jit(lambda x: x)\n")
    assert lint_source(ok, rel="src/repro/m.py", only={"DL003"}) == []


def test_dl003_module_level_jit_is_clean():
    ok = "import jax\nf = jax.jit(lambda x: x)\n"
    assert lint_source(ok, rel="src/repro/m.py", only={"DL003"}) == []


def test_dl003_static_argnames_typo_fires():
    bad = ("import functools, jax\n"
           "@functools.partial(jax.jit, static_argnames=('mode',))\n"
           "def f(x, *, mod='and'):\n    return x\n")
    out = lint_source(bad, rel="src/repro/m.py", only={"DL003"})
    assert "DL003" in codes(out) and "mode" in out[0].message


def test_dl003_unhashable_static_default_fires():
    bad = ("import functools, jax\n"
           "@functools.partial(jax.jit, static_argnames=('cfg',))\n"
           "def f(x, *, cfg=[1, 2]):\n    return x\n")
    assert "DL003" in codes(lint_source(bad, rel="src/repro/m.py",
                                        only={"DL003"}))


def test_dl003_per_call_varying_static_fires():
    bad = ("import functools, jax\n"
           "@functools.partial(jax.jit, static_argnames=('minsup',))\n"
           "def f(x, *, minsup=0):\n    return x\n\n"
           "def g(x, threshold):\n"
           "    return f(x, minsup=int(threshold))\n")
    out = lint_source(bad, rel="src/repro/m.py", only={"DL003"})
    assert "DL003" in codes(out)
    assert any("per-call-varying" in f.message for f in out)


def test_dl003_bounded_static_call_is_clean():
    ok = ("import functools, jax\n"
          "@functools.partial(jax.jit, static_argnames=('mode',))\n"
          "def f(x, *, mode='and'):\n    return x\n\n"
          "def g(x):\n    return f(x, mode='andnot')\n")
    assert lint_source(ok, rel="src/repro/m.py", only={"DL003"}) == []


# ---------------------------------------------------------------------------
# DL004 — mesh-axis discipline
# ---------------------------------------------------------------------------

def test_dl004_psum_over_cls_literal_fires():
    bad = ("import jax\nfrom jax.sharding import PartitionSpec as P\n"
           "spec = P('block', 'cls')\n"
           "def body(x):\n    return jax.lax.psum(x, 'cls')\n")
    out = lint_source(bad, rel="src/repro/m.py", only={"DL004"})
    assert codes(out) == ["DL004"] and "cls" in out[0].message


def test_dl004_psum_over_cls_axes_name_fires():
    bad = ("import jax\ncls_axes = ('cls',)\n"
           "def body(x):\n    return jax.lax.psum(x, cls_axes)\n")
    assert "DL004" in codes(lint_source(bad, rel="src/repro/m.py",
                                        only={"DL004"}))


def test_dl004_all_gather_along_cls_is_sanctioned():
    ok = ("import jax\nfrom jax.sharding import PartitionSpec as P\n"
          "spec = P('cls')\n"
          "def body(x):\n"
          "    return jax.lax.all_gather(x, 'cls', axis=0, tiled=True)\n")
    assert lint_source(ok, rel="src/repro/m.py", only={"DL004"}) == []


def test_dl004_undeclared_literal_axis_fires():
    bad = ("import jax\nfrom jax.sharding import PartitionSpec as P\n"
           "spec = P('block')\n"
           "def body(x):\n    return jax.lax.psum(x, 'pod')\n")
    out = lint_source(bad, rel="src/repro/m.py", only={"DL004"})
    assert codes(out) == ["DL004"] and "undeclared" in out[0].message


def test_dl004_declared_literal_axis_is_clean():
    ok = ("import jax\nfrom jax.sharding import PartitionSpec as P\n"
          "spec = P('block')\n"
          "def body(x):\n    return jax.lax.psum(x, 'block')\n")
    assert lint_source(ok, rel="src/repro/m.py", only={"DL004"}) == []


def test_dl004_variable_axes_are_not_guessed():
    ok = ("import jax\n"
          "def body(x, tid_axes):\n"
          "    return jax.lax.psum(x, tid_axes)\n")
    assert lint_source(ok, rel="src/repro/m.py", only={"DL004"}) == []


# ---------------------------------------------------------------------------
# baseline ratchet + the real repo
# ---------------------------------------------------------------------------

def test_baseline_ratchet_new_and_stale(tmp_path):
    src = "import numpy as np\nx = np.asarray(rows)\n"
    findings = lint_source(src, rel=CORE, only={"DL001"})
    assert len(findings) == 1
    bl = tmp_path / "baseline.json"
    save_baseline(findings, bl)
    baseline = load_baseline(bl)
    # same findings -> clean
    new, stale = diff_baseline(findings, baseline)
    assert new == [] and stale == []
    # a second, different finding -> NEW
    two = lint_source(src + "y = np.asarray(cols)\n", rel=CORE,
                      only={"DL001"})
    new, stale = diff_baseline(two, baseline)
    assert len(new) == 1 and stale == []
    # finding fixed -> STALE entry must fail until the baseline shrinks
    new, stale = diff_baseline([], baseline)
    assert new == [] and len(stale) == 1


def test_baseline_fingerprint_survives_line_drift():
    src = "import numpy as np\nx = np.asarray(rows)\n"
    drifted = "import numpy as np\n\n\n# moved\nx = np.asarray(rows)\n"
    a = lint_source(src, rel=CORE, only={"DL001"})
    b = lint_source(drifted, rel=CORE, only={"DL001"})
    assert a[0].line != b[0].line
    assert a[0].fingerprint == b[0].fingerprint


def test_repo_lints_clean_against_committed_baseline():
    """The CI contract: `python -m tools.devicelint src tests benchmarks`
    passes, and core/ + kernels/ carry ZERO baseline entries (ISSUE 10
    acceptance)."""
    findings = lint_paths(["src", "tests", "benchmarks"])
    baseline = load_baseline()
    new, stale = diff_baseline(findings, baseline)
    assert new == [], [str(f) for f in new]
    assert stale == [], stale
    assert [e for e in baseline
            if e["path"].startswith(("src/repro/core/",
                                     "src/repro/kernels/"))] == []


# ---------------------------------------------------------------------------
# runtime guard (core/guards.py) — the other half of DL001
# ---------------------------------------------------------------------------

def test_guard_arms_jax_transfer_guard():
    import jax
    from repro.core.guards import device_purity_guard, host_sync, \
        purity_guard_active

    assert not purity_guard_active()
    with device_purity_guard():
        assert purity_guard_active()
        assert (jax.config.jax_transfer_guard_device_to_host
                == "disallow")
        with host_sync("test escape"):
            # escape: syncs allowed, and the activity flag reflects it
            assert not purity_guard_active()
            assert (jax.config.jax_transfer_guard_device_to_host
                    == "allow")
        assert purity_guard_active()
    assert not purity_guard_active()


def test_host_sync_requires_justification():
    from repro.core.guards import host_sync
    with pytest.raises(AssertionError):
        with host_sync(""):
            pass


def test_guarded_mine_matches_unguarded():
    """FrontierScheduler.run() is guard-wrapped internally; a full mine
    under an OUTER guard as well must still resolve its accounting
    through the annotated escapes only."""
    import random
    from repro.core.eclat import mine_bitmap
    from repro.core.guards import device_purity_guard
    from repro.core.oracle import mine_bruteforce

    rng = random.Random(3)
    db = [sorted(set(rng.choices(range(7), k=rng.randint(1, 4))))
          for _ in range(25)]
    expected = mine_bruteforce(db, 3)
    with device_purity_guard():
        out, _ = mine_bitmap(db, 3, scheme="eclat", early_stop=True,
                             block_words=4)
    assert out == expected
