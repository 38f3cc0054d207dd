"""Chip benchmark of federated MoCo v3 training (LW-FedSSL and FedMoCo).

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator it starts on and
prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced, a
``breakdown``; ``checks`` (each compared number beside its limit) comes
last, and the same numbers end standard error. Exits non-zero and prints
no result when JAX finds no TPU, fewer chips than the cell asks for, or
no program beside the benchmark.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
