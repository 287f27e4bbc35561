"""Batched serving example: prefill a batch of prompts, decode with the
slot server, report tokens/s (deliverable b, serving flavor).

    PYTHONPATH=src python examples/serve_batch.py --arch mamba2-370m
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.kernels import ops
from repro.launch.serve import main as serve_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()
    ops.enable_compile_cache()
    serve_main(["--arch", args.arch, "--preset", "smoke",
                "--requests", str(args.requests), "--gen", str(args.gen)])


if __name__ == "__main__":
    main()
