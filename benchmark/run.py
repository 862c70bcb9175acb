"""One new process, one cell, one last line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. It fails (non-zero, no last line) when JAX comes up on anything but
a TPU or finds fewer chips than the cell needs; it never falls back. It sets
no `HOROVOD_*` variable and passes the program no optional argument its
defaults would fill: a cell runs what a user gets.
"""

import time

T0 = time.perf_counter()   # before the heavy imports: set-up counts them

import argparse  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmark.harness import runner, spec
    cell = spec.load_cell(args.workload)
    line = runner.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t0=T0)
    print(line, flush=True)


if __name__ == "__main__":
    main()
