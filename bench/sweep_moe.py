#!/usr/bin/env python3
"""``bench/sweep.py`` for the MoE served cells: the knee of a cell whose
mix names ``serve_moe``, found as ``bench/sweep.py`` finds it, with
``serve_moe``'s model in place of the dense one.

    python3 bench/sweep_moe.py --workload serve.a3b-x4.chat --seed 1 \
        --requests 64 --rates 4 6 24

Not part of a benchmark run; exits non-zero without a TPU.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import serve_moe  # noqa: E402
import sweep  # noqa: E402

# sweep.main imports ``serve`` when it runs: give it this driver, which
# carries serve's helpers and its own ``model_config``
sys.modules["serve"] = serve_moe

if __name__ == "__main__":
    sys.exit(sweep.main())
