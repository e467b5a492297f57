"""A traced run of one cell, read by the program's own spans.

    python3 bench/phases.py --workload bert_base_rope_allmiss.templ_r80 \\
        --seed 7 --seconds 30

Runs what ``bench/run.py --trace 1`` runs (its traced window already
holds ``spanfold``'s keys: the program's ``memo.*`` spans and the
device's "XLA Modules" line), with the breakdown read by ``spanfold``. It
prints ``run.py``'s traced result line, in which ``breakdown`` names
idle gaps by the program's spans and device operations by their program,
with one more entry, ``program``: the readings of ``spanfold.READINGS``
and ``idle_by_phase``, each left out where the program recorded no
span. The seven per-layer metrics read the
same numbers as under ``run.py``: the keys they read are unchanged.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def folding():
    """Inside, ``tracefold.load`` keeps the last trace it loaded in the
    dict this yields, under ``"trace"``; the breakdown's ``top_ops`` and
    ``idle_gaps`` are spanfold's."""
    from bench import spanfold, tracefold
    kept: dict = {}
    saved = tracefold.load, tracefold.top_ops, tracefold.idle_gaps
    load = saved[0]

    def load_kept(path):
        kept["trace"] = tr = load(path)
        return tr

    tracefold.load = load_kept
    tracefold.top_ops, tracefold.idle_gaps = (spanfold.top_ops,
                                              spanfold.idle_gaps)
    try:
        yield kept
    finally:
        tracefold.load, tracefold.top_ops, tracefold.idle_gaps = saved


def traced_run(workload: str, seed: int, seconds: float, **kw) -> dict:
    """``run.run_cell(..., trace=True)``'s result line with ``program``
    added; ``kw`` as ``run_cell`` takes them."""
    from bench import run, spanfold
    with folding() as kept:
        res = run.run_cell(workload, seed, seconds, True, **kw)
    res["program"] = spanfold.readings(kept["trace"])
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import run
    try:
        res = traced_run(args.workload, args.seed, args.seconds)
    except run.NoChip as e:
        run.log(f"bench: {e}")
        return 2
    prog = res["program"]
    run.log("[program] " + ", ".join(
        f"{k} {prog[k]:.6g}" for k in prog if k != "idle_by_phase"))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
