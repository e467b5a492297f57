"""Reads, on several seeds in one process, the numbers a cell's output
check compares, for the program and for its control, and judges each with
the cell's own limits.

    python3 bench/control.py --workload bert_base_rope_allmiss.templ_r80 \\
        --seeds 101,102,103 --seconds 4 --level conservative

For each seed it sets the cell up, serves a short window at the cell's own
load, and reads, on the same sampled prompts: the program's numbers, and
the control's, the int8 reference (W8A8) put in the program's place and
judged by the float32 reference. With ``--level`` it then autotunes the
threshold at that level, serves the same requests again on the same
store, and reads the program's numbers once more: the memoized answers
that neighbour hits give, beside the all-miss ones as their witness. One
JSON line per seed; ``correct`` is what the cell's limits say of each.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def judged(numbers: dict, limits: dict) -> dict:
    return dict(numbers, correct=all(numbers[k] <= v
                                     for k, v in limits.items()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--level", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax.numpy as jnp
    import numpy as np
    from bench.run import Cell, NoChip, check_numbers, log, output_check
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            c = Cell(args.workload, seed)
        except NoChip as e:
            log(f"control: {e}")
            return 2
        limits = c.cfg["check"]["limits"]
        reqs = c.requests(args.seconds)
        n = min(int(c.cfg["check"]["sample"]), len(reqs))
        sample = np.random.default_rng([seed, 3]).choice(
            len(reqs), n, replace=False).tolist()
        win, counters, kept, _ = c.window(reqs, args.seconds, sample)
        row = {"seed": seed, "served": len(win.served),
               "hit_share": counters["n_hits"]
               / max(1, counters["n_layer_attempts"])}
        kept_tuned = None
        if args.level:
            bs = c.cfg["calibration"]["batch"]
            rng = np.random.default_rng([seed, 2])
            c.sess.autotune([{"tokens": jnp.asarray(
                c.templates.sample(bs, rng))}], level=args.level)
            win2, counters2, kept_tuned, _ = c.window(reqs, args.seconds,
                                                      sample)
            row["tuned"] = {"level": args.level,
                            "threshold": c.sess.spec.runtime.threshold,
                            "hit_share": counters2["n_hits"]
                            / max(1, counters2["n_layer_attempts"])}
        c.release()
        out = output_check(c.cfg, c.task, c.ref, c.model, c.params, reqs,
                           kept, control=True)
        row["program"] = judged(check_numbers(out["gap"], out["err"]),
                                limits)
        row["control"] = judged(check_numbers(out["control_gap"],
                                              out["control_err"]), limits)
        if kept_tuned is not None:
            t = output_check(c.cfg, c.task, c.ref, c.model, c.params, reqs,
                             kept_tuned)
            row["tuned"].update(judged(check_numbers(t["gap"], t["err"]),
                                       limits))
        print(json.dumps(row), flush=True)
        del c, kept, kept_tuned
    return 0


if __name__ == "__main__":
    sys.exit(main())
