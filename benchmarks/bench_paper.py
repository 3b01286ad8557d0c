"""Paper reproduction benchmarks.

* Table IV analogue: #Cands / #Nodes / Ratio per dataset x minsup.
* Figures 7-15 analogue: #comparisons and runtime for the six schemes
  (Eclat, dEclat, PrePost+ each with/without Early Stopping) on the nine
  dataset replicas, plus the device bitmap engine's word-op metric.

Replicas are statistical stand-ins for the FIMI/KONECT sets (offline
container); the paper's qualitative claims under test:
  C1 ES reduces comparisons on every dataset (guaranteed);
  C2 reductions are large on high-ratio (sparse) data, negligible on
     dense low-ratio data;
  C3 #cands/#nodes are identical across schemes at a given minsup.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List

from repro.core.oracle import mine
from repro.core.eclat import mine_bitmap
from repro.data import make_dataset

SCHEMES = ("eclat", "declat", "prepost")


def run_dataset(name: str, minsup_levels: List[int], runs: int = 1,
                ) -> List[Dict]:
    db, _ = make_dataset(name)
    rows: List[Dict] = []
    for li, ms in enumerate(minsup_levels):
        base: Dict[str, Dict] = {}
        for scheme in SCHEMES:
            for es in (False, True):
                t0 = time.perf_counter()
                for _ in range(runs):
                    out, st = mine(db, ms, scheme, early_stop=es)
                dt = (time.perf_counter() - t0) / runs
                base[f"{scheme}{'-ES' if es else ''}"] = {
                    "comparisons": st.comparisons,
                    "runtime_s": dt,
                    "cands": st.candidates,
                    "nodes": st.nodes,
                    "aborts": st.es_aborts,
                    "F": len(out),
                }
        # device engine (word-op metric)
        for es in (False, True):
            t0 = time.perf_counter()
            out_b, st_b = mine_bitmap(db, ms, "eclat", early_stop=es,
                                      block_words=8)
            base[f"bitmap-eclat{'-ES' if es else ''}"] = {
                "comparisons": st_b.word_ops,
                "runtime_s": time.perf_counter() - t0,
                "cands": st_b.candidates,
                "nodes": st_b.nodes,
                "aborts": st_b.kernel_aborts + st_b.screened_out,
                "F": len(out_b),
            }
        rows.append({"dataset": name, "minsup_level": li + 1,
                     "minsup": ms, "schemes": base})
    return rows


def table_iv(rows: List[Dict]) -> str:
    """#Cands / #Nodes / Ratio (identical across schemes — checked)."""
    out = ["| dataset | minSup | #Cands | #Nodes | Ratio |",
           "|---|---|---|---|---|"]
    for r in rows:
        s = r["schemes"]["eclat"]
        for other in ("declat", "prepost"):
            # PrePost+ proposes the same candidate count modulo the
            # final-singleton classes; nodes must match exactly.
            assert r["schemes"][other]["nodes"] == s["nodes"], r["dataset"]
        ratio = s["cands"] / max(s["nodes"], 1)
        out.append(f"| {r['dataset']} | {r['minsup']} | {s['cands']:.3g} "
                   f"| {s['nodes']:.3g} | {ratio:.2f} |")
    return "\n".join(out)


def figures(rows: List[Dict]) -> str:
    """Comparisons + runtime per scheme (the Figures 7-15 content)."""
    out = ["| dataset | minSup | scheme | comparisons | saved | "
           "runtime_s | aborts |",
           "|---|---|---|---|---|---|---|"]
    for r in rows:
        for scheme in ("eclat", "declat", "prepost", "bitmap-eclat"):
            std = r["schemes"][scheme]
            es = r["schemes"][scheme + "-ES"]
            saved = 1 - es["comparisons"] / max(std["comparisons"], 1)
            out.append(
                f"| {r['dataset']} | {r['minsup']} | {scheme} "
                f"| {std['comparisons']:.4g} -> {es['comparisons']:.4g} "
                f"| {saved:.1%} | {std['runtime_s']:.3f} -> "
                f"{es['runtime_s']:.3f} | {es['aborts']} |")
    return "\n".join(out)


def _smoke_datasets() -> Dict[str, tuple]:
    """The CI smoke matrix (ROADMAP "widen the smoke dataset set"):

    * ``powerlaw`` — sparse retail-like, high candidate/node ratio: the
      regime where bitmap-engine ES word-op savings are large;
    * ``dense``    — correlated tabular (chess-like), ratio ~ 1;
    * ``longpat``  — highly correlated tabular with long frequent
      patterns (maxlen ~ n_cols): the dense/long-pattern regime where
      N-list schemes (PrePost+) are the interesting engine.
    """
    from repro.data.transactions import (gen_dense_tabular,
                                         gen_powerlaw_baskets)

    return {
        "powerlaw": (gen_powerlaw_baskets(n_trans=300, n_items=200,
                                          avg_trans_len=6, seed=0), 3),
        "dense": (gen_dense_tabular(n_trans=500, n_cols=9,
                                    vals_per_col=4, seed=0), 175),
        "longpat": (gen_dense_tabular(n_trans=400, n_cols=10,
                                      vals_per_col=3, correlation=0.95,
                                      n_classes=2, seed=1), 120),
    }


# Frozen PR 3 reference: DevicePrePost issued one dispatch per class
# member's sibling window, which cost 1021 fused calls on the longpat
# smoke regime.  The shared frontier scheduler (ISSUE 4) must beat it.
_PR3_LONGPAT_PREPOST_DEVICE_CALLS = 1021


def run_smoke(out_path: str = "BENCH_smoke.json") -> Dict:
    """CI benchmark smoke: the three-regime dataset matrix through both
    device engines (bitmap Eclat and PrePost+), ES vs full.

    Hard-asserts the paper's headline effect where it is guaranteed
    (identical result sets everywhere; ``word_ops_saved_frac > 0`` and
    PrePost+ comparison savings on the sparse powerlaw replica; ES never
    increases PrePost+ comparisons anywhere) plus the ISSUE 4 frontier
    acceptance (PrePost+ ``device_calls`` on longpat strictly below the
    PR 3 per-member-dispatch baseline), and writes the stats JSON so
    every CI run leaves a bench artifact — including the allocator
    telemetry (``peak_rows`` / ``peak_codes``, ``compactions``,
    post-compaction occupancy) that
    benchmarks/check_bench_regression.py diffs vs the committed
    baseline.
    """
    from repro.core.prepost import mine_prepost_device

    report: Dict = {"datasets": {}}
    for name, (db, minsup) in _smoke_datasets().items():
        t0 = time.perf_counter()
        out_es, st_es = mine_bitmap(db, minsup, "eclat", early_stop=True,
                                    block_words=8)
        t_es = time.perf_counter() - t0
        t0 = time.perf_counter()
        out_no, st_no = mine_bitmap(db, minsup, "eclat", early_stop=False,
                                    block_words=8)
        t_no = time.perf_counter() - t0
        t0 = time.perf_counter()
        out_pes, st_pes = mine_prepost_device(db, minsup, early_stop=True)
        t_pes = time.perf_counter() - t0
        t0 = time.perf_counter()
        out_pno, st_pno = mine_prepost_device(db, minsup, early_stop=False)
        t_pno = time.perf_counter() - t0

        # Density-adaptive diffset representation (ISSUE 6): the same DB
        # through the adaptive engine at 1-word blocks — the 300-500
        # transaction replicas then span 10-16 blocks, which gives the
        # diffset scan's zero-mass block skip something to skip — vs the
        # tidset engine at the SAME granularity (the fair word_ops
        # reference the dense acceptance gate compares against).
        akw = dict(block_words=1, diff_density=0.3, diff_hysteresis=0.05)
        t0 = time.perf_counter()
        out_aes, st_aes = mine_bitmap(db, minsup, "adaptive",
                                      early_stop=True, **akw)
        t_aes = time.perf_counter() - t0
        t0 = time.perf_counter()
        out_ano, st_ano = mine_bitmap(db, minsup, "adaptive",
                                      early_stop=False, **akw)
        t_ano = time.perf_counter() - t0
        _, st_tes = mine_bitmap(db, minsup, "eclat", early_stop=True,
                                block_words=1)

        assert out_es == out_no == out_pes == out_pno == out_aes == out_ano, (
            f"{name}: engines disagree")
        assert st_pes.comparisons <= st_pno.comparisons, (
            f"{name}: ES increased PrePost+ comparisons")
        cmp_saved = 1.0 - st_pes.comparisons / max(st_pno.comparisons, 1)
        report["datasets"][name] = {
            "dataset": {"n_trans": len(db), "minsup": minsup},
            "frequent_itemsets": len(out_es),
            "frequent_children": sum(1 for s in out_es if len(s) >= 2),
            "es": {**st_es.as_dict(), "wall_s": round(t_es, 3)},
            "full": {**st_no.as_dict(), "wall_s": round(t_no, 3)},
            "word_ops_saved_frac": st_es.word_ops_saved_frac,
            "prepost": {
                "es": {**st_pes.as_dict(), "wall_s": round(t_pes, 3)},
                "full": {**st_pno.as_dict(), "wall_s": round(t_pno, 3)},
                "comparisons_saved_frac": round(cmp_saved, 4),
            },
            "adaptive": {
                "knobs": akw,
                "es": {**st_aes.as_dict(), "wall_s": round(t_aes, 3)},
                "full": {**st_ano.as_dict(), "wall_s": round(t_ano, 3)},
                # tidset engine at the same 1-word block granularity:
                # the apples-to-apples reference for the representation
                # saving (word_ops_full is already granularity-shared)
                "tidset_es_word_ops": st_tes.word_ops,
            },
        }
        print(f"smoke {name}: F={len(out_es)}, "
              f"word_ops_saved_frac={st_es.word_ops_saved_frac:.3f}, "
              f"prepost_cmp_saved={cmp_saved:.3f}, "
              f"device_calls={st_es.device_calls}+"
              f"{st_pes.device_calls}, "
              f"compactions={st_es.compactions}+{st_pes.compactions}, "
              f"peak={st_es.peak_rows}r/{st_pes.peak_codes}c, "
              f"scatters={st_es.child_scatters}/{st_es.candidates}cand "
              f"({st_es.scatter_words}+{st_pes.scatter_words}w), "
              f"adaptive_word_ops={st_aes.word_ops} "
              f"(tidset@bw1={st_tes.word_ops})",
              file=sys.stderr)

    # Dispatch-pipeline demo (ISSUE 7): at the default pair_chunk every
    # DFS wave drains into one group (nothing to overlap), so the
    # occupancy demo runs the powerlaw regime at a small chunk where
    # each wave splits into several groups and the double-buffered ring
    # actually interleaves host assembly with device execution.  The
    # occupancy metric is deterministic (ring state at dispatch, not
    # timing), so it is assert-able in CI; assemble_s/resolve_s are the
    # informational assembly-vs-device time split
    # (check_bench_regression.py ignores fields it does not know).
    pl_db, pl_ms = _smoke_datasets()["powerlaw"]
    pipe_chunk = 1024
    _, st_ser = mine_bitmap(pl_db, pl_ms, "eclat", early_stop=True,
                            block_words=8, pair_chunk=pipe_chunk,
                            inflight=1)
    _, st_pipe = mine_bitmap(pl_db, pl_ms, "eclat", early_stop=True,
                             block_words=8, pair_chunk=pipe_chunk,
                             inflight=2)
    report["pipeline"] = {
        "regime": "powerlaw", "pair_chunk": pipe_chunk,
        "serial": {"ring_overlap_frac": st_ser.ring_overlap_frac,
                   "assemble_s": round(st_ser.assemble_s, 6),
                   "resolve_s": round(st_ser.resolve_s, 6)},
        "pipelined": {"ring_overlap_frac": st_pipe.ring_overlap_frac,
                      "assemble_s": round(st_pipe.assemble_s, 6),
                      "resolve_s": round(st_pipe.resolve_s, 6)},
    }

    # Per-bucket chunk-width autotuning (ISSUE 7): at a deliberately
    # small base pair_chunk the width table widens every chunk (smoke
    # operands are far below the reference operand size), collapsing
    # device_calls at bit-identical per-pair work.
    auto_chunk = 64
    auto = {"regime": "powerlaw", "base_pair_chunk": auto_chunk}
    _, st_boff = mine_bitmap(pl_db, pl_ms, "eclat", early_stop=True,
                             block_words=8, pair_chunk=auto_chunk,
                             autotune_chunk=False)
    _, st_bon = mine_bitmap(pl_db, pl_ms, "eclat", early_stop=True,
                            block_words=8, pair_chunk=auto_chunk,
                            autotune_chunk=True)
    auto["bitmap"] = {
        "device_calls": {"off": st_boff.device_calls,
                         "on": st_bon.device_calls},
        "word_ops": {"off": st_boff.word_ops, "on": st_bon.word_ops},
        "scatter_words": {"off": st_boff.scatter_words,
                          "on": st_bon.scatter_words},
    }
    _, st_poff = mine_prepost_device(pl_db, pl_ms, early_stop=True,
                                     pair_chunk=auto_chunk,
                                     autotune_chunk=False)
    _, st_pon = mine_prepost_device(pl_db, pl_ms, early_stop=True,
                                    pair_chunk=auto_chunk,
                                    autotune_chunk=True)
    auto["prepost"] = {
        "device_calls": {"off": st_poff.device_calls,
                         "on": st_pon.device_calls},
        "comparisons": {"off": st_poff.comparisons,
                        "on": st_pon.comparisons},
        "scatter_words": {"off": st_poff.scatter_words,
                          "on": st_pon.scatter_words},
    }
    report["autotune"] = auto
    print(f"smoke pipeline: occupancy {st_ser.ring_overlap_frac:.2f} -> "
          f"{st_pipe.ring_overlap_frac:.2f} @chunk={pipe_chunk}; "
          f"autotune device_calls bitmap "
          f"{st_boff.device_calls}->{st_bon.device_calls}, prepost "
          f"{st_poff.device_calls}->{st_pon.device_calls} "
          f"@chunk={auto_chunk}", file=sys.stderr)

    # Write the artifact BEFORE the acceptance asserts: when a gate
    # trips, CI must still upload the telemetry needed to debug it.
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    # Survivor-only materialization (ISSUE 5): every engine's child
    # scatter count equals the frequent children, never the candidate
    # count, ES on or off.
    for name, ds in report["datasets"].items():
        n_children = ds["frequent_children"]
        for run in (ds["es"], ds["full"],
                    ds["prepost"]["es"], ds["prepost"]["full"]):
            assert run["child_scatters"] == n_children, (
                f"{name}: scattered {run['child_scatters']} children, "
                f"{n_children} are frequent")
    pl = report["datasets"]["powerlaw"]
    assert pl["word_ops_saved_frac"] > 0, "ES saved no word ops (powerlaw)"
    assert pl["prepost"]["comparisons_saved_frac"] > 0, (
        "ES saved no PrePost+ comparisons (powerlaw)")
    lp_calls = report["datasets"]["longpat"]["prepost"]["es"]["device_calls"]
    assert lp_calls < _PR3_LONGPAT_PREPOST_DEVICE_CALLS, (
        f"frontier batching regressed: longpat PrePost+ device_calls "
        f"{lp_calls} >= PR 3's {_PR3_LONGPAT_PREPOST_DEVICE_CALLS}")
    # ISSUE 6 acceptance: on the dense regime the density-adaptive
    # tidset->diffset switch must strictly beat the tidset engine's
    # word_ops at the same block granularity (the diffset rows of the
    # high-support subtrees go mostly zero-mass, and the skip-aware
    # work counter stops charging those blocks).
    da = report["datasets"]["dense"]["adaptive"]
    assert da["es"]["word_ops"] < da["tidset_es_word_ops"], (
        f"adaptive switching saved nothing on dense: word_ops "
        f"{da['es']['word_ops']} >= tidset {da['tidset_es_word_ops']}")
    # ISSUE 7 acceptance: the pipelined run overlaps drain groups on the
    # powerlaw regime (occupancy strictly above the serial baseline,
    # which is 0.0 by construction) ...
    pp = report["pipeline"]
    assert (pp["pipelined"]["ring_overlap_frac"]
            > pp["serial"]["ring_overlap_frac"]), (
        f"pipelining overlapped nothing: occupancy "
        f"{pp['pipelined']['ring_overlap_frac']} <= serial "
        f"{pp['serial']['ring_overlap_frac']}")
    # ... and per-bucket widths reduce device_calls at unchanged
    # per-pair work (word_ops / comparisons / scatter_words).
    at = report["autotune"]
    for eng, work_key in (("bitmap", "word_ops"),
                          ("prepost", "comparisons")):
        calls, work = at[eng]["device_calls"], at[eng][work_key]
        scat = at[eng]["scatter_words"]
        assert calls["on"] < calls["off"], (
            f"autotune reduced no {eng} device_calls: {calls}")
        assert work["on"] == work["off"], (
            f"autotune changed {eng} {work_key}: {work}")
        assert scat["on"] == scat["off"], (
            f"autotune changed {eng} scatter_words: {scat}")
    print(f"smoke ok -> {out_path}", file=sys.stderr)
    return report


def run_full(out_path: str = "BENCH_full.json", *, scale: float = 1.0,
             datasets: List[str] = None, block: int = None, cls: int = 1,
             seed: int = 0) -> Dict:
    """Paper-scale tier (ISSUE 9): the kosarak/accidents/pumsb replicas
    at (scaled) paper row counts, streamed into the sharded row store
    and mined by ``DistributedMiner`` on a 2-D ``(block, cls)`` mesh.

    Records a per-dataset minsup-ladder *trajectory* — wall clock,
    ``word_ops``/``word_ops_full``, ``device_calls`` and the per-host
    peak device words of the slab — into ``BENCH_full.json`` (schema in
    benchmarks/README.md) next to the smoke baseline.  Counters are
    deterministic integer math over seeded streams; wall times are
    informational (check_bench_regression.py gates only the counters).

    ``scale`` multiplies every replica's transaction count (CI runs
    ``--full --scale 0.1`` on one CPU device so the path cannot rot
    between hardware runs); minsups are relative, so the mined regime
    is scale-invariant.  The packing happens once per dataset at the
    smallest ladder rung; each rung mines the rows still frequent at
    its own threshold (the BitmapDB row order is support-ascending, so
    that is a suffix slice — no repacking).
    """
    import jax
    import numpy as np

    from repro.core.bitmap import BitmapDB
    from repro.core.distributed import DistributedMiner
    from repro.data.transactions import PAPER_REPLICAS, stream_paper_dataset
    from repro.launch.mesh import make_mining_mesh

    names = datasets or list(PAPER_REPLICAS)
    mesh = make_mining_mesh(block=block, cls=cls)
    report: Dict = {
        "tier": "full",
        "scale": scale,
        "seed": seed,
        "mesh": {"block": int(mesh.shape["block"]),
                 "cls": int(mesh.shape["cls"]),
                 "devices": jax.device_count(),
                 "hosts": jax.process_count()},
        "datasets": {},
    }
    hosts = max(1, jax.process_count())
    for name in names:
        t0 = time.perf_counter()
        bdb, minsups = stream_paper_dataset(name, scale=scale, seed=seed)
        pack_s = time.perf_counter() - t0
        miner = DistributedMiner(mesh, scheme="eclat", early_stop=True,
                                 inflight=2, autotune_chunk=True)
        traj = []
        # Largest rung first: coarse runs are cheap and fail fast.
        for ms in sorted(minsups, reverse=True):
            keep = np.flatnonzero(bdb.supports >= ms)
            sub = BitmapDB(items=[bdb.items[i] for i in keep],
                           bitmaps=bdb.bitmaps[keep],
                           supports=bdb.supports[keep],
                           n_trans=bdb.n_trans, minsup=ms,
                           block_words=bdb.block_words)
            t0 = time.perf_counter()
            out, st = miner.mine_packed(sub, ms)
            wall = time.perf_counter() - t0
            traj.append({
                "minsup": int(ms),
                "wall_s": round(wall, 3),
                "word_ops": st.word_ops,
                "word_ops_full": st.word_ops_full,
                "word_ops_saved_frac": round(st.word_ops_saved_frac, 4),
                "device_calls": st.device_calls,
                "peak_device_words_per_host":
                    -(-st.peak_device_words // hosts),
                "frequent_itemsets": len(out),
            })
            print(f"full {name} minsup={ms}: F={len(out)} "
                  f"wall={wall:.2f}s word_ops={st.word_ops} "
                  f"calls={st.device_calls} "
                  f"peak_words/host={traj[-1]['peak_device_words_per_host']}",
                  file=sys.stderr)
        report["datasets"][name] = {
            "dataset": {"n_trans": bdb.n_trans, "n_items_frequent":
                        bdb.n_items, "n_blocks": bdb.n_blocks,
                        "block_words": bdb.block_words,
                        "pack_s": round(pack_s, 3)},
            "trajectory": traj,
        }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"full tier ok -> {out_path}", file=sys.stderr)
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny synthetic dataset; assert ES word-op "
                         "savings and write a BENCH_*.json artifact")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale replica tier on the 2-D mining "
                         "mesh; writes a BENCH_full.json trajectory")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="full tier: transaction-count multiplier "
                         "(CI uses 0.1)")
    ap.add_argument("--datasets", nargs="*", default=None,
                    help="full tier: subset of paper replicas to run")
    ap.add_argument("--mesh-block", type=int, default=None,
                    help="full tier: block-axis size (default: all "
                         "devices / cls)")
    ap.add_argument("--mesh-cls", type=int, default=1,
                    help="full tier: cls-axis size")
    ap.add_argument("--out", default=None,
                    help="JSON output path (default BENCH_smoke.json / "
                         "BENCH_full.json)")
    args = ap.parse_args()
    from repro.cache import configure_compile_cache
    configure_compile_cache()
    if args.smoke:
        run_smoke(args.out or "BENCH_smoke.json")
        return
    if args.full:
        run_full(args.out or "BENCH_full.json", scale=args.scale,
                 datasets=args.datasets, block=args.mesh_block,
                 cls=args.mesh_cls)
        return
    print("full paper sweep lives in benchmarks/run.py "
          "(python -m benchmarks.run --sections paper); "
          "use --smoke for the CI smoke bench or --full for the "
          "paper-scale tier", file=sys.stderr)
    sys.exit(2)


def csv_rows(rows: List[Dict]) -> List[str]:
    """name,us_per_call,derived lines for benchmarks.run."""
    out = []
    for r in rows:
        for scheme, v in r["schemes"].items():
            us = v["runtime_s"] * 1e6
            out.append(
                f"paper/{r['dataset']}/ms{r['minsup_level']}/{scheme},"
                f"{us:.0f},comparisons={v['comparisons']};F={v['F']}")
    return out


if __name__ == "__main__":
    main()
