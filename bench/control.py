#!/usr/bin/env python3
"""The control of the check that decides `correct`.

The configurations state exact answers: every stored triple that matches,
each once. The store has a path of its own that breaks that guarantee —
a shard whose snapshot would not load is served as an empty hole
(``mark_shard_failed``) — and the control is the cell run on that path:
the same seeds, traffic and window, with shard 0 degraded after the
reopen. Every one of its runs must come out not correct; its smallest
readings are the upper readings the limits in ``bench/check.py`` sit
under. The benchmark's own runs never run it.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

prints one JSON line per seed: the numbers compared, with the program's
and the control's readings side by side.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from bench import run  # noqa: E402


def degrade_one_shard(svc) -> None:
    svc.mark_shard_failed(0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            sound = run.run_cell(run.ROOT, args.workload, seed, args.seconds, False)
            control = run.run_cell(run.ROOT, args.workload, seed, args.seconds, False,
                                   prepare=degrade_one_shard)
        except run.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": {k: v["value"] for k, v in sound["checks"].items()},
            "control": {k: v["value"] for k, v in control["checks"].items()},
            "program_correct": sound["correct"], "control_correct": control["correct"],
            "attempted": [sound["attempted"], control["attempted"]]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
