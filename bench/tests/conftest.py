"""CPU rehearsal tests of the benchmark harness: ``python -m pytest
bench/tests``.  They run the harness's pieces on the CPU at small sizes;
no number they see is a chip measurement."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)
