"""Run one cell of the benchmark and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for.  With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer ones, read from a profiler
trace of part of the window.  Everything but the last line of standard
output goes to standard error; the numbers that decide ``correct`` come
last there, each beside its limit.

Exits non-zero, with no result, when JAX finds no device listed in
``bench/peaks.json`` or fewer chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"


def enable_cache():
    """Keep every compiled program, however small, in ``CACHE_DIR``, so
    that only the first run of a cell in a checkout compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_peaks(chips: int):
    """The peaks of the device JAX found, or an error message when it is
    not one the table lists or there are fewer than ``chips``."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    with open(ROOT / "bench" / "peaks.json") as f:
        table = json.load(f)
    if devices[0].platform != "tpu" or kind not in table:
        return None, (f"no accelerator listed in bench/peaks.json: JAX "
                      f"found {len(devices)} x {kind!r} "
                      f"({devices[0].platform})")
    if len(devices) < chips:
        return None, f"the cell asks for {chips} chips, JAX found " \
                     f"{len(devices)}"
    return table[kind], None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib.harness import Harness
    h = Harness(ROOT)
    cell = h.cell(args.workload)

    enable_cache()
    peaks, err = device_peaks(int(cell["chips"]))
    if err:
        print(f"bench/run.py: {err}", file=sys.stderr)
        return 1

    from bench.lib.cell import run_cell, trace_dir_for
    result = run_cell(
        h, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), peaks=peaks, t_start=T_START,
        trace_dir=trace_dir_for(ROOT, args.workload, args.seed))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
