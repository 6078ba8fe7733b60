"""Training launcher.

Local (CPU-sim) execution with the full production loop:
    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke --steps 20

Cluster posture: on a real fleet this same entrypoint runs per host under
`jax.distributed.initialize()` (flags below); data is sharded per host by
(host_id, num_hosts); the dry-run path (`--dryrun`) AOT-compiles the step
for the production mesh instead of executing.
"""
import argparse
import tempfile

from repro.configs import get_config, SHAPES, smoke_shape
from repro.data import MarkovChainData, SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamWConfig
from repro.runtime import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny batch (CPU-sized)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--profile", default="megatron",
                    choices=["megatron", "fsdp", "serve"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--data", choices=["markov", "uniform"], default="markov")
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--num-hosts", type=int, default=1)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        shape = smoke_shape("train")
    else:
        shape = SHAPES[args.shape]
        assert shape.kind == "train", "use serve.py for inference shapes"

    data_cls = MarkovChainData if args.data == "markov" else SyntheticLMData
    data = data_cls(cfg, shape, seed=0, num_hosts=args.num_hosts,
                    host_id=args.host_id)
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix=f"{args.arch}_ckpt_")
    trainer = Trainer(
        cfg, shape, data,
        TrainerConfig(total_steps=args.steps,
                      ckpt_every=max(args.steps // 5, 5),
                      ckpt_dir=ckpt, log_every=max(args.steps // 20, 1)),
        opt_cfg=AdamWConfig(warmup_steps=min(100, args.steps // 3 or 1),
                            total_steps=args.steps),
        compress=args.compress_grads)
    res = trainer.run_with_recovery()
    for m in res["metrics"]:
        print(f"step {m['step']:6d}  loss {m['loss']:.4f}  lr {m['lr']:.2e}  "
              f"{m['step_s']*1e3:.0f} ms")
    print(f"done: {res['final_step']} steps, {res['restarts']} restarts, "
          f"{len(res['stragglers'])} straggler flags; checkpoints: {ckpt}")


if __name__ == "__main__":
    main()
