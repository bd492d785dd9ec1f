"""Readings that a cell's correctness limit is set from, on the chip.

    python bench/limits.py --workload <cell> --seconds <s> --seeds 1,2,3

One process, one set-up per seed: the cell's engine serves its traffic for
``--seconds`` exactly as a run's window does, then the same sample of
finished requests that a run checks is compared with the plain reference
(the program's reading: the mean, and the widest, gap by which a served
token's logit lies below the reference's best) and with the control, the
reference in int8 (the gap, under the reference, of the token the control
puts first).  Each is judged by the run's own ``run.verdict`` against the
limit in ``cells/<cell>.json``: the program has to come out correct and the
control not.  Prints one JSON line per seed.  The lower reading is the
largest program mean gap over a dozen seeds or more, the upper the
smallest control mean gap; the limit lies between them (``PERF.md``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402
import serving  # noqa: E402


def readings(found: dict, seed: int, seconds: float) -> dict:
    cfg, traffic, nums = found["config"], found["traffic"], found["numbers"]
    arch = run.arch_of(cfg)
    t = time.perf_counter()
    scale = serving.act_step(cfg, arch, seed)
    eng = serving.build_engine(cfg, arch, seed, scale)
    serving.warm_up(eng, cfg)
    w = serving.run_window(eng, cfg, traffic, seed, seconds,
                           settle_tokens=nums["check_tokens"])
    n_failed = serving.failed(w)
    serving.free(eng)
    ref = serving.check(cfg, arch, traffic, seed, w, nums["check_tokens"],
                        scale, with_control=True)
    control = dict(ref, mean_logit_gap=ref["control_mean_logit_gap"])
    return {"seed": seed, "failed": n_failed, **ref,
            "correct": run.verdict(ref, n_failed, nums)[0],
            "control_correct": run.verdict(control, 0, nums)[0],
            "wall_s": time.perf_counter() - t}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    found = run.find_cell(args.workload)
    run.check_device(found["cell"]["chips"])
    run.enable_cache()
    for s in args.seeds.split(","):
        print(json.dumps(readings(found, int(s), args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
