"""End-to-end training driver: a ~100M-parameter model for a few hundred
steps with checkpointing, failure recovery and metrics (deliverable b).

    PYTHONPATH=src python examples/train_100m.py --steps 300

CPU-budget note: a full 300-step run at the default sizes is hours on this
single-core container; `--steps 30` demonstrates the same loop (loss on the
induction/copy task falls well below the unigram floor either way).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.kernels import ops
from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()
    ops.enable_compile_cache()
    train_main(["--arch", args.arch, "--preset", "100m",
                "--steps", str(args.steps), "--batch", str(args.batch),
                "--seq", str(args.seq), "--lr", "3e-3",
                "--checkpoint-dir", "/tmp/repro_100m_ckpt",
                "--checkpoint-every", "50",
                "--metrics", "/tmp/repro_100m_metrics.jsonl"])


if __name__ == "__main__":
    main()
