"""Find a cell's knee: the highest arrival rate the engine sustains
without a growing backlog, by one sweep of rates in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 2,3,4,5 [--out bench/.out/sweep-<cell>.json]

Builds the cell's engine once, then offers the cell's traffic at each
rate in turn (the mix's pre-roll, then ``--seconds`` measured), and
prints per rate: requests due and finished in the window, the backlog
(submitted, not finished) at the window's start and end, TTFT and
inter-token p95, and output tokens per second.  Between rates every
request left is cancelled and the engine drained.  The cell's fixed rate
is set by hand from this, at about four fifths of the knee, and recorded
with the sweep in its traffic file.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, device_peaks, enable_cache  # noqa: E402


def backlog(requests, t: float) -> int:
    return sum(1 for r in requests if r.submit_t is not None
               and r.submit_t <= t and (r.finish_t is None or r.finish_t > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench.lib.harness import Harness
    enable_cache()
    h = Harness(ROOT)
    cell = h.cell(args.workload)
    peaks, err = device_peaks(int(cell["chips"]))
    if err:
        print(f"bench/sweep.py: {err}", file=sys.stderr)
        return 1

    import jax
    from repro.runtime import make_engine
    from bench.lib import record, serve
    from bench.lib.model import arch_config, draw_params, engine_config
    conf = h.config(cell["config"])
    mix = h.traffic(cell["traffic"])
    gen = h.generator(mix)
    cfg = arch_config(conf)
    engine = make_engine(cfg, draw_params(cfg, args.seed), engine_config(
        conf, gen.max_len(mix), use_kernel=True))
    serve.warm_up(engine, cfg.vocab_size)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        plan = gen.generate(m, vocab=cfg.vocab_size, seed=args.seed + i,
                            seconds=args.seconds)
        base = 10_000_000 * (i + 1)
        plan = [dataclasses.replace(p, rid=base + p.rid) for p in plan]
        out = serve.drive(engine, plan, preroll=float(m["preroll_s"]),
                          seconds=args.seconds,
                          tracing=serve.Tracing(None))
        run = record.Run(start=out["start"], end=out["end"],
                         requests=out["requests"], steps=out["steps"],
                         setup_s=0.0, shapes={}, peaks=peaks)
        due = run.due_in_window()
        row = dict(
            rate=rate, due=len(due),
            finished=sum(1 for r in out["requests"]
                         if run.in_window(r.finish_t)),
            backlog_start=backlog(out["requests"], out["start"]),
            backlog_end=backlog(out["requests"], out["end"]),
            never_submitted=sum(1 for r in due if r.submit_t is None),
            ttft_p95_ms=record.percentile(record.ttft_s(run), 95) * 1e3,
            itl_p95_ms=record.percentile(record.itl_s(run), 95) * 1e3,
            tokens_per_s=record.tokens_in_window(run) / run.seconds,
            iterations=len(record.window_steps(run)))
        rows.append(row)
        print(json.dumps(row), flush=True)
        for r in out["requests"]:
            if r.submit_t is not None and r.finish is None:
                engine.cancel(r.rid)
        while engine.step():
            engine.poll_deltas()
        engine.poll_deltas()
    stats = jax.devices()[0].memory_stats() or {}
    result = {"workload": args.workload, "seconds": args.seconds,
              "preroll_s": mix["preroll_s"], "rows": rows,
              "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
              "total_s": time.monotonic() - T_START}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
