#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload wide_deep.zipf --seed 7 \
        --seconds 30 --trace 0

Runs from the root of a checkout, on the machine that holds the chips.
It refuses to run (exit 2, no result) where JAX finds no TPU or fewer
chips than the cell asks for. The last line of standard output is one JSON
object: ``correct``, ``attempted`` (steps in the window), ``failed`` (steps
whose loss is not finite), ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared beside its limit,
which are also the last lines of standard error.

JAX's compilation cache is the program's (``repro.launch.compile_cache``):
``$JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache`` in the
checkout, so only a cell's first run in a checkout compiles.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _finite(checks):
    """JSON has no infinity: a number that is not finite is written as a
    string."""
    return {k: {f: (v if not isinstance(v, float) or math.isfinite(v)
                    else str(v)) for f, v in c.items()}
            for k, c in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"no program under {src}: run from a checkout of the repo",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, HERE)
    from manifest import Manifest
    manifest = Manifest(ROOT, HERE)
    cell = manifest.workload(args.workload)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program goes into the cache, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX finds {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 2

    import harness
    log = lambda s: print(s, file=sys.stderr, flush=True)
    result = harness.run(manifest, args.workload, args.seed, args.seconds,
                         trace=bool(args.trace), t0=T0, log=log)
    result["checks"] = _finite(result["checks"])
    for line in harness.checks_lines(result["checks"]):
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
