"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up (weights, records, the server, the warm-up and every compile) is
reported as ``setup_s``; the window then measures for ``--seconds``.
With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from the profiler's trace
of the window and from the program's spans and counters.  Every run holds
what the timed path produced to the plain reference and prints each
number compared beside its limit; ``correct`` is false when one fails.
``--control`` puts the cell's lower-precision control in the program's
place, the run that shows the comparison can fail.

The run needs the chips its cell asks for: without a TPU it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, control: bool = False,
             t_start: float = None, cell=None) -> dict:
    """One run of one cell; returns the result line.  ``require_tpu=False``
    lets a test drive the rest of a run on the CPU; ``control=True`` runs
    the cell's lower-precision control in the program's place; a test may
    hand in ``cell`` with its sizes cut."""
    t_start = T_START if t_start is None else t_start
    if cell is None:
        cell = harness.resolve_cell(harness.load_spec(), workload)
    import jax
    devs = (harness.require_chips(cell.chips) if require_tpu
            else jax.devices()[:cell.chips])
    meter = harness.CompileMeter()
    system = harness.load_module(harness.system_path(cell.config["system"]),
                                 "chipbench_system")
    outcome = system.run(cell, seed, seconds, trace, devs, meter, t_start,
                         control=control)
    return harness.result_line(cell, outcome, trace)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the cell's lower-precision control in the "
                         "program's place: correct must come out false")
    args = ap.parse_args()
    harness.enable_compile_cache()
    harness.emit(run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), control=args.control))


if __name__ == "__main__":
    main()
