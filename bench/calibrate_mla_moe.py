"""``bench/calibrate.py``'s readings for a cell of the MLA + MoE driver.

    python3 bench/calibrate_mla_moe.py --workload deepseek-v3.decode \
        --seeds 11,12,13

The same program, float8 control and faults (a decode step that returns
its cache unchanged; a token altered where it is produced), judged by the
same rule; only the reference is this driver's
(``drivers/serve_mla_moe.reference``, over ``bench.reference.mla_moe``)
in place of the dense one that ``bench.check`` runs, and each reading
carries the mean gap over every position beside the widest
(``drivers/serve_mla_moe.Gaps``), the cell's second limit.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import calibrate, check  # noqa: E402
from bench.drivers import serve_mla_moe  # noqa: E402

if __name__ == "__main__":
    check.reference = serve_mla_moe.reference
    check.gap = serve_mla_moe.Gaps.of
    sys.exit(calibrate.main())
