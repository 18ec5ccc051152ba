"""Example: LM serving with continuous batching.

Thin wrapper over repro.launch.serve with a reduced zoo config: the same
ServeEngine the serving driver runs (add ``--router`` or ``--paged`` for
the replicated or paged-cache deployment).

Run:  PYTHONPATH=src python examples/serve_lm.py --arch mamba2-780m
"""
import sys

from repro.launch.serve import main

if __name__ == "__main__":
    main(["--reduced"] + (sys.argv[1:] if len(sys.argv) > 1 else
                          ["--arch", "qwen3-4b", "--requests", "6",
                           "--max-new", "8"]))
