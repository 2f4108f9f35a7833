"""Run one benchmark cell once; see bench/harness.py.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
