"""Batched serving example: continuous slot batcher over prefill/decode.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import sys

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    sys.argv = [sys.argv[0], "--preset", "lm-tiny", "--requests", "10",
                "--new", "12", "--slots", "4"]
    enable_compile_cache()
    main()
