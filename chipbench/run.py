"""The chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, its driver
and its per-layer metric readers are found by name under ``chipbench/``
(see ``chipbench/harness.py``). The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``), then ``checks``, each
number compared beside its limit. The process exits non-zero and prints
no result where JAX finds no TPU, or fewer chips than the cell asks for.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    # JAX's persistent compilation cache lives at one fixed path inside the
    # checkout, also where the environment names another directory that
    # two checkouts could share; the program's own cache setup
    # (``setup_compile_cache``) takes the directory from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # the script's directory would shadow standard modules (trace.py)
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    from chipbench import harness

    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
