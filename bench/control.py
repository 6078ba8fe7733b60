"""Read the check's two ends on a cell: the program's widest logit gap
(the lower reading) and the control's (the upper), seed by seed, in one
process so the programs compile once.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--out bench/.out/control-<cell>.json]

Each seed is a whole run of the cell (set-up, pre-roll, a window of
``--seconds``, the check) that also reads the control: the reference
computed with float8 matrix products, the step below the bfloat16 the
configurations state.  A limit is sound only between the largest program
reading and the smallest control reading.  The benchmark's own runs never
run this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, device_peaks, enable_cache  # noqa: E402


def readings(h, cell: str, seeds, seconds: float, peaks: dict,
             log=print) -> list:
    from bench.lib.cell import run_cell
    rows = []
    for seed in seeds:
        r = run_cell(h, cell, seed=seed, seconds=seconds, trace=False,
                     peaks=peaks, t_start=time.monotonic(), lower=True)
        row = {"seed": seed, "correct": r["correct"],
               "program": r["check"]["max_logit_gap"]["value"],
               "tokens": r["check"]["checked_tokens"]["value"],
               "control": r["control"]["value"]}
        rows.append(row)
        log(json.dumps(row))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench.lib.harness import Harness
    enable_cache()
    h = Harness(ROOT)
    peaks, err = device_peaks(int(h.cell(args.workload)["chips"]))
    if err:
        print(f"bench/control.py: {err}", file=sys.stderr)
        return 1
    rows = readings(h, args.workload,
                    [int(s) for s in args.seeds.split(",")], args.seconds,
                    peaks, log=lambda m: print(m, flush=True))
    summary = {"workload": args.workload, "rows": rows,
               "program_max": max(r["program"] for r in rows),
               "control_min": min(r["control"] for r in rows),
               "total_s": time.monotonic() - T_START}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
