"""Chip benchmark of the ZapRAID block service: one cell, one run.

Run from the root of a checkout, on a machine with the chip(s) the cell
asks for:

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cells, their configurations, traffic and metrics are named in
``BENCHMARK.json`` at the root and found as files beside this one (see
``specs.py``).  With ``--trace 0`` the result reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, the device's
busy time and a breakdown from a profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
traced), then ``checks``, each number compared with its limit.  The last
lines of standard error repeat the checks.  Without a TPU, with fewer
chips than the cell asks for, or without the program's sources beside
the benchmark, it exits non-zero and prints no result.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = CHECKOUT / ".jax_cache"


def parse_args(argv=None, faults=()):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    if faults:
        p.add_argument("--fault", choices=faults, required=True)
    return p.parse_args(argv)


def main(argv=None, faults=()) -> None:
    args = parse_args(argv, faults)
    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"run.py: the program's sources are not at {src}")
    sys.path.insert(0, str(src))
    # the cache lives in the checkout, at a fixed path, whatever the
    # environment names; the program's entry points take it from here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    import faults as fault_mod
    import harness
    import specs

    use_compile_cache()
    cell = specs.load_cell(args.workload, trace=bool(args.trace))
    fault = getattr(args, "fault", None)
    harness.log(f"jax {jax.__version__}; cell {cell.name}; seed {args.seed}; "
                f"fault {fault}; compile cache {CACHE_DIR}")
    try:
        with fault_mod.planted(fault):
            result = harness.run_cell(cell, args.seed, args.seconds,
                                      bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        sys.exit(f"run.py: {e}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
