"""Batched serving demo: continuous-batching greedy decode on CPU.

    PYTHONPATH=src python examples/serve_lm.py
"""

from repro.launch.serve import main
from repro.runtime import compile_cache

if __name__ == "__main__":
    compile_cache.enable()
    main()
