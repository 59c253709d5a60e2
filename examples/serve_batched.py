"""Batched serving of a small model with continuous-batching-lite slots.

  PYTHONPATH=src python examples/serve_batched.py --arch gemma2-2b
"""
from repro.launch.serve import main

if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
