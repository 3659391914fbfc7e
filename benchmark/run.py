"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. Everything about the cell is data: BENCHMARK.json names its
configuration file (benchmark/configs/), its traffic file
(benchmark/traffic/, whose ``kind`` names the generator module under
rtbench/kinds/) and its per-layer metrics (benchmark/layer_metrics/, each
naming a reader module under rtbench/readers/). A later PR adds a cell by
adding such files and entries and edits nothing here.

Earlier lines of standard output carry the set-up breakdown, the count of
compilations inside the window and the generator's lateness; the last line
is the result, one JSON object. Without a TPU, or with fewer chips than the
cell asks for, the exit code is not 0 and no result is printed.

Once the result line is out the process ends itself (``os._exit`` after a
flush): ``serve.shutdown()`` leaves the engine's scheduler thread alive,
and a run of PR 25 that had printed its result never exited (50
chip-minutes). Run a cell under ``timeout`` all the same.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python lets us

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from rtbench import common, manifest

    cell = manifest.load_cell(args.workload)
    section = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in cell[section]}
    wanted = set(units)

    def emit(correct, attempted, failed, values, device, breakdown):
        missing = wanted - set(values)
        if not args.trace and missing:
            raise RuntimeError(f"run produced no value for {sorted(missing)}")
        common.emit_result(
            correct, attempted, failed,
            {k: v for k, v in values.items() if k in wanted}, units, device,
            breakdown if args.trace else None)

    kind = importlib.import_module("rtbench.kinds." + cell["traffic"]["kind"])
    kind.run({"cell": cell, "seed": args.seed, "seconds": args.seconds,
              "trace": bool(args.trace), "emit": emit,
              "clock": common.SetupClock(T_START)})
    return 0


def _exit_now(code: int) -> None:
    """Leave without waiting for a thread the program left behind. The
    kinds have stopped and waited for every process they started."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    try:
        _exit_now(main())
    except SystemExit as e:   # no TPU, a bad argument: the code it names
        _exit_now(e.code if isinstance(e.code, int) else int(bool(e.code)))
    except BaseException:     # noqa: BLE001 - print it, then leave at once
        import traceback

        traceback.print_exc()
        _exit_now(1)
