"""Run one cell of the chip benchmark once:

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the cell's chips.
See ``chipbench/bench.py``.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench.bench import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
