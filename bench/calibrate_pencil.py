"""Readings that the pencil cell's correctness limit is set from:
``bench/calibrate.py`` with the pencil driver's control registered.

    python3 bench/calibrate_pencil.py --workload pencil2d.r4096.x4 \\
        --seeds 1,2,... --control-seeds 7,8,9 --seconds 1
"""
import os
import sys

import calibrate
import run

if __name__ == "__main__":
    driver = run.load_module(os.path.join(run.HERE, "drivers",
                                          "pencil_step.py"),
                             "bench_driver_pencil_step")
    calibrate.CONTROLS["pencil_step"] = driver.control
    sys.exit(calibrate.main())
